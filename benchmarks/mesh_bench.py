"""Mesh-parallel SPMD runtime: the partition loop vs one shard_map dispatch.

Warm-path comparison of the two executor modes over the same dataset:
the per-partition Python loop (P fused dispatches + P device_gets per
query) against the SPMD partition mesh (operands stacked along a leading
partition axis, ONE ``shard_map`` dispatch for all partitions —
``runtime/spmd.py``).  Three benches cover the three lowered paths:

  mesh_index_chain   Figure-6 btree chain select (``plancache.run_all``)
  mesh_select        non-indexed range scan (``spmd.batched_range_masks``)
  mesh_agg_merge     fused filter+aggregate chain with local aggregation
                     (``spmd.batched_select_aggregate`` / chain agg mode)

Hard assertions: mesh rows must equal loop rows exactly, a warm mesh
query must ship zero host->device bytes and retrace nothing, and the
mesh mode must beat the warm loop.  The gain is dispatch amortization
(P per-partition dispatches + device_gets collapse into one), so the
threshold scales with how much of the query IS dispatch: the chain and
aggregate benches run device-side end to end and must hit >= 2x at full
size (32 partitions, 4-device mesh); the scan-path select still filters
row output per partition on the host, so its bar is >= 1.2x.  Smoke
sizes leave almost no dispatch cost to amortize (250-row partitions) —
there the bars are 1.05x / 0.7x, a regression tripwire rather than a
performance claim; scripts/verify.sh runs ``--smoke``.

The mesh needs >= 4 devices and runs in this process on the devices
JAX holds (a chip belongs to the one process that touched JAX first, so
the bench never hands the mesh to a child).  With fewer devices it fails
and says so.  A CPU rehearsal gets four host devices from its caller,
which sets ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before
Python starts (``scripts/verify.sh`` and CI's mesh leg do).

Usage: PYTHONPATH=src python -m benchmarks.mesh_bench [--smoke]
"""

from __future__ import annotations

import argparse
import datetime as dt
import sys

from ._timing import stopwatch, timed as _timed

FULL_USERS, FULL_MSGS, FULL_PARTS = 2000, 16000, 32
SMOKE_USERS, SMOKE_MSGS, SMOKE_PARTS = 600, 2000, 8
MESH_DEVICES = 4


def _canon(rows):
    return sorted(repr(sorted(r.items(), key=lambda kv: kv[0]))
                  for r in rows)


def _plans():
    from repro.core import algebra as A
    # selective range: the warm cost is the chain dispatch itself, not
    # host row decode, so dispatch amortization is what gets measured
    lo, hi = dt.datetime(2010, 1, 1), dt.datetime(2010, 3, 1)
    mlo = dt.datetime(2014, 1, 15)
    return {
        "mesh_index_chain": A.select(
            A.scan("MugshotUsers"),
            pred=lambda r: lo <= r["user-since"] <= hi,
            fields=["user-since"], ranges={"user-since": (lo, hi)},
            ranges_exact=True),
        # message-id has no index: lowers to scan + range mask, which
        # the mesh runs as one stacked spmd_range_mask dispatch
        "mesh_select": A.select(
            A.scan("MugshotMessages"),
            pred=lambda r: 100 <= r["message-id"] <= 900,
            fields=["message-id"], ranges={"message-id": (100, 900)},
            ranges_exact=True),
        "mesh_agg_merge": A.aggregate(
            A.select(A.scan("MugshotMessages"),
                     pred=lambda r: r["timestamp"] >= mlo,
                     fields=["timestamp"],
                     ranges={"timestamp": (mlo, None)},
                     ranges_exact=True),
            {"c": ("count", "*"), "av": ("avg", "author-id"),
             "mx": ("max", "timestamp")}),
    }


def _run_local(smoke: bool = False) -> list:
    """The actual bench; requires >= MESH_DEVICES jax devices."""
    import jax

    from repro.configs.tinysocial import build_dataverse
    from repro.storage.query import run_query

    n_dev = len(jax.devices())
    assert n_dev >= MESH_DEVICES, \
        f"mesh bench needs {MESH_DEVICES} devices, have {n_dev} " \
        f"(set XLA_FLAGS={_FORCE_FLAG} before jax imports)"
    nu, nm, parts = (SMOKE_USERS, SMOKE_MSGS, SMOKE_PARTS) if smoke \
        else (FULL_USERS, FULL_MSGS, FULL_PARTS)
    _, ds = build_dataverse(nu, nm, num_partitions=parts,
                            flush_threshold=512)
    repeat = 5 if smoke else 20
    bars = {"mesh_index_chain": 1.05, "mesh_select": 0.7,
            "mesh_agg_merge": 1.05} if smoke else \
        {"mesh_index_chain": 2.0, "mesh_select": 1.2,
         "mesh_agg_merge": 2.0}
    rows = []
    for name, plan in _plans().items():
        # warm both modes fully (trace + upload), then time steady state
        res_l, _ = run_query(plan, ds, vectorize=True)
        run_query(plan, ds, vectorize=True)
        ((_, ex_l), t_loop) = _timed(
            lambda p=plan: run_query(p, ds, vectorize=True), repeat)
        res_m, _ = run_query(plan, ds, vectorize=True, mesh=MESH_DEVICES)
        run_query(plan, ds, vectorize=True, mesh=MESH_DEVICES)
        ((_, ex_m), t_mesh) = _timed(
            lambda p=plan: run_query(p, ds, vectorize=True,
                                     mesh=MESH_DEVICES), repeat)
        assert _canon(res_l) == _canon(res_m), \
            f"{name}: mesh rows diverge from the loop " \
            f"({len(res_l)} vs {len(res_m)})"
        assert ex_m.stats.spmd_dispatches >= 1, \
            f"{name}: mesh mode never dispatched SPMD"
        assert ex_m.stats.h2d_bytes == 0, \
            f"{name}: warm mesh query shipped {ex_m.stats.h2d_bytes} B " \
            f"host->device"
        assert ex_m.stats.kernel_retraces == 0, \
            f"{name}: warm mesh query retraced " \
            f"{ex_m.stats.kernel_retraces} cores"
        assert ex_l.stats.h2d_bytes == 0 \
            and ex_l.stats.kernel_retraces == 0, \
            f"{name}: loop baseline was not warm"
        speedup = t_loop / t_mesh
        assert speedup >= bars[name], \
            f"{name}: mesh only {speedup:.2f}x vs the partition loop " \
            f"(need >= {bars[name]}x at {parts} partitions)"
        rows.append({
            "bench": name,
            "us_per_call": t_mesh * 1e6,
            "us_loop": t_loop * 1e6,
            "speedup": round(speedup, 2),
            "partitions": parts,
            "spmd_dispatches": ex_m.stats.spmd_dispatches,
            "h2d_warm": ex_m.stats.h2d_bytes,
            "retraces_warm": ex_m.stats.kernel_retraces,
            "derived": f"{speedup:.1f}x vs {parts}-partition loop, "
                       f"{ex_m.stats.spmd_dispatches} SPMD dispatch(es), "
                       f"warm ships 0 B",
        })
    return rows


def run(smoke: bool = False) -> list:
    """Bench entry point for ``benchmarks.run``: in-process, on the
    devices this process holds."""
    import jax
    have = len(jax.devices())
    if have < MESH_DEVICES:
        raise RuntimeError(
            f"mesh bench needs {MESH_DEVICES} devices, JAX sees {have} "
            f"({jax.devices()[0].platform}); on CPU, start Python with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{MESH_DEVICES}")
    return _run_local(smoke)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="small dataset, fewer repeats (CI gate)")
    args = p.parse_args()
    with stopwatch() as sw:
        out = run(smoke=args.smoke)
    print("name,us_mesh,us_loop,speedup,partitions,h2d_warm,retraces_warm")
    for r in out:
        print(f"{r['bench']},{r['us_per_call']:.1f},{r['us_loop']:.1f},"
              f"{r['speedup']},{r['partitions']},{r['h2d_warm']},"
              f"{r['retraces_warm']}")
    print(f"# mesh_bench done in {sw.seconds:.1f}s "
          f"({'smoke' if args.smoke else 'full'})", file=sys.stderr)


if __name__ == "__main__":
    main()
