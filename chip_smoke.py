#!/usr/bin/env python3
"""Bring-up smoke of the columnar BDMS path on a TPU.

    python chip_smoke.py              # one chip: the whole main path
    python chip_smoke.py --chips 4    # four chips: loop vs mesh=4 only

Builds the paper's Mugshot dataverse (``configs/tinysocial`` schema and
generator, seeded) at 2**22 MugshotMessages and 2**18 MugshotUsers over
16 partitions through ``insert_batch``, with btree indexes on
``timestamp``, ``author-id`` and ``user-since`` and an ngram(3) index on
``MugshotUsers.name``; flush thresholds that leave each partition about
``FLUSHES`` components, then one forced merge per partition.  Then it
runs seven queries through ``run_query(plan, datasets, vectorize=True)``
— a btree index-chain select, a scan range select, a fused
filter+aggregate (count, int sum, datetime max), an int count/min/max
aggregate, a group-by, a join under a group-by and a fuzzy
edit-distance select — and checks each against the reference semantics:
the row engine for index-driven plans, a plain Python computation over
the generated records for the scans.  Every query must stay on the
device path (no fallback, at least one kernel dispatch), its warm
repeat must ship nothing and retrace nothing, and every Pallas kernel
the store keeps must have run.  It then crashes and recovers
MugshotMessages and re-queries, and runs the concurrent serving harness
over a fresh MugshotMessages dataset of the same layout (ingest through
feeds, flushes and merges, columnar queries; zero torn reads, zero lost
acks, no query errors).

Fails (non-zero exit, no result line) unless JAX's first device is a
TPU.  Earlier lines report per-phase wall and compile time, compile
cache hits, which kernels ran as Pallas or as XLA, the device pool's
resident bytes and the device's peak memory.  The last line is the JSON
result.
"""

from __future__ import annotations

import argparse
import datetime as dt
import json
import os
import sys
import time
from collections import defaultdict
from contextlib import contextmanager

ROOT = os.path.dirname(os.path.abspath(__file__))

N_MESSAGES = 1 << 22
N_USERS = 1 << 18
N_PARTITIONS = 16
FLUSHES = 8                     # flushed components per partition
SEED = 0
# kernels whose Pallas branch the one-chip run must exercise
PALLAS_KERNELS = ("range_mask", "fused_filter_aggregate",
                  "sorted_intersect_mask", "edit_distances")

DAY = dt.timedelta(days=1)
T0 = dt.datetime(2014, 1, 1)    # gen_messages: timestamps in [T0, T0+120d)
U0 = dt.datetime(2008, 1, 1)    # gen_users: user-since in [U0, U0+6y)


def log(msg: str) -> None:
    print(msg, flush=True)


class Smoke:
    """One run: the dataverse, its reference records, and the checks."""

    def __init__(self, n_messages: int, n_users: int, meter) -> None:
        self.n_messages = n_messages
        self.n_users = n_users
        self.meter = meter
        self.ds = {}
        self.msgs = []
        self.users = []

    # -- phases ------------------------------------------------------------
    @contextmanager
    def phase(self, name: str):
        hits0, _miss0, comp0 = self.meter.snapshot()
        t0 = time.perf_counter()
        yield
        hits, _miss, comp = self.meter.snapshot()
        log(f"phase {name}: wall_s={time.perf_counter() - t0:.3f} "
            f"compile_s={comp - comp0:.3f} cache_hits={hits - hits0}")

    def build(self) -> None:
        from repro.configs import tinysocial as ts
        from repro.core import adm
        from repro.core.lsm import TieredMergePolicy
        from repro.storage.dataset import PartitionedDataset

        dv = adm.Dataverse("TinySocial")
        ut = dv.create_type(ts.user_type())
        mt = dv.create_type(ts.message_type())
        # no automatic merges: flushes accumulate, the forced merge below
        # then leaves several components per partition
        policy = TieredMergePolicy(k=1 << 20)
        users = PartitionedDataset("MugshotUsers", ut, "id", N_PARTITIONS,
                                   self._flush_at(self.n_users),
                                   merge_policy=policy)
        msgs = PartitionedDataset("MugshotMessages", mt, "message-id",
                                  N_PARTITIONS, self._flush_at(self.n_messages),
                                  merge_policy=policy)
        users.create_index("user-since")
        users.create_index("name", "ngram", 3)
        msgs.create_index("timestamp")
        msgs.create_index("author-id")
        self.users = ts.gen_users(self.n_users, SEED)
        self.msgs = ts.gen_messages(self.n_messages, self.n_users, SEED + 1)
        step = 1 << 15
        for recs, d in ((self.users, users), (self.msgs, msgs)):
            for j in range(0, len(recs), step):
                d.insert_batch(recs[j:j + step])
        for d in (users, msgs):
            for part in d.partitions:
                comps = [c for c in part.primary.components if c.valid]
                if len(comps) >= 2:          # the two oldest, once
                    part.primary.merge(comps[-2:])
        n_comp = [len([c for c in p.primary.components if c.valid])
                  for p in msgs.partitions]
        log(f"dataverse: messages={len(self.msgs)} users={len(self.users)} "
            f"partitions={N_PARTITIONS} message components per partition "
            f"min={min(n_comp)} max={max(n_comp)}")
        if min(n_comp) < 2:
            raise RuntimeError(f"expected several components: {n_comp}")
        self.ds = {"MugshotUsers": users, "MugshotMessages": msgs}

    @staticmethod
    def _flush_at(n_records: int) -> int:
        return max(n_records // (N_PARTITIONS * FLUSHES), 1)

    # -- queries -----------------------------------------------------------
    def plans(self) -> dict:
        """name -> (plan, reference()): reference semantics independent of
        ``columnar/`` and ``kernels/``."""
        from repro.core import algebra as A
        from repro.fuzzy import fuzzy_predicate

        i0, i1 = T0 + 31 * DAY, T0 + 31 * DAY + dt.timedelta(hours=6)
        a0, a1 = 1000, 1063
        f0 = T0 + 59 * DAY
        b1 = self.n_users // 2
        g0, g1 = T0 + 31 * DAY, T0 + 38 * DAY
        j0, j1 = T0 + 40 * DAY, T0 + 41 * DAY
        u0, u1 = U0, U0 + 2 * 365 * DAY
        # every name shares the "User Number " grams, so this spec leaves
        # hundreds of distinct candidates per partition at 2**18 users:
        # verification runs the dictionary DP kernel, not the host DP
        ed_spec = ("name", "ed", "User Number 123456", 2)

        def sel(ds, fld, lo, hi, scan=False):
            return A.select(A.scan(ds), pred=lambda r: lo <= r[fld] <= hi,
                            fields=[fld], ranges={fld: (lo, hi)},
                            ranges_exact=scan,
                            hints=["skip-index"] if scan else [])

        M = self.msgs
        plans = {
            "index_chain_select": (
                sel("MugshotMessages", "timestamp", i0, i1), "row"),
            "scan_range_select": (
                sel("MugshotMessages", "author-id", a0, a1, scan=True),
                lambda: self._validated(
                    m for m in M if a0 <= m["author-id"] <= a1)),
            "fused_filter_aggregate": (
                A.aggregate(sel("MugshotMessages", "timestamp", f0,
                                T0 + 365 * DAY, scan=True),
                            {"c": ("count", "*"), "s": ("sum", "author-id"),
                             "mx": ("max", "timestamp")}),
                lambda: [self._agg([m for m in M if m["timestamp"] >= f0])]),
            # int32-exact columns and no sum read: the Pallas aggregate
            "int_minmax_aggregate": (
                A.aggregate(sel("MugshotMessages", "author-id", 0, b1,
                                scan=True),
                            {"c": ("count", "*"), "n": ("count", "author-id"),
                             "lo": ("min", "message-id"),
                             "hi": ("max", "message-id")}),
                lambda: [self._minmax([m for m in M
                                       if m["author-id"] <= b1])]),
            "group_by": (
                A.group_by(sel("MugshotMessages", "timestamp", g0, g1,
                               scan=True), ["author-id"],
                           {"c": ("count", "*"),
                            "mx": ("max", "timestamp")}),
                lambda: self._groups(
                    [m for m in M if g0 <= m["timestamp"] <= g1])),
            "join_group_by": (
                A.group_by(A.join(
                    sel("MugshotMessages", "timestamp", j0, j1, scan=True),
                    sel("MugshotUsers", "user-since", u0, u1, scan=True),
                    ["author-id"], ["id"]), ["author-id"],
                    {"c": ("count", "*"), "mx": ("max", "timestamp")}),
                lambda: self._groups(self._joined(j0, j1, u0, u1))),
            "fuzzy_ed_select": (
                A.select(A.scan("MugshotUsers"), pred=fuzzy_predicate(ed_spec),
                         fields=["name"], fuzzy=ed_spec), "row"),
        }
        return plans

    def _validated(self, recs):
        mt = self.ds["MugshotMessages"].dtype
        return [mt.validate(r) for r in recs]

    @staticmethod
    def _agg(recs):
        return {"c": len(recs), "s": sum(r["author-id"] for r in recs),
                "mx": max((r["timestamp"] for r in recs), default=None)}

    @staticmethod
    def _minmax(recs):
        ids = [r["message-id"] for r in recs]
        return {"c": len(recs), "n": len(recs), "lo": min(ids, default=None),
                "hi": max(ids, default=None)}

    @staticmethod
    def _groups(recs):
        g = defaultdict(list)
        for r in recs:
            g[r["author-id"]].append(r["timestamp"])
        return [{"author-id": a, "c": len(ts), "mx": max(ts)}
                for a, ts in g.items()]

    def _joined(self, j0, j1, u0, u1):
        ok = {u["id"] for u in self.users if u0 <= u["user-since"] <= u1}
        return [m for m in self.msgs
                if j0 <= m["timestamp"] <= j1 and m["author-id"] in ok]

    def check_queries(self, tag: str, plans: dict, mesh=None) -> dict:
        """Run every plan cold and warm; return name -> cold rows."""
        from repro.storage.query import run_query
        got = {}
        for name, (plan, ref) in plans.items():
            with self.phase(f"{tag}.{name}"):
                t0 = time.perf_counter()
                rows, ex = run_query(plan, self.ds, vectorize=True,
                                     mesh=mesh)
                cold_s = time.perf_counter() - t0
                want = run_query(plan, self.ds)[0] if ref == "row" \
                    else ref()
                st = ex.stats
                if canon(rows) != canon(want):
                    raise AssertionError(
                        f"{tag}.{name}: {len(rows)} rows differ from the "
                        f"reference's {len(want)}")
                if st.fallback_reasons:
                    raise AssertionError(f"{tag}.{name}: fell back "
                                         f"{dict(st.fallback_reasons)}")
                if st.kernel_dispatches <= 0:
                    raise AssertionError(f"{tag}.{name}: no kernel ran")
                if mesh is not None and st.spmd_dispatches < 1:
                    raise AssertionError(f"{tag}.{name}: no SPMD dispatch")
                t0 = time.perf_counter()
                warm, wex = run_query(plan, self.ds, vectorize=True,
                                      mesh=mesh)
                warm_s = time.perf_counter() - t0
                ws = wex.stats
                if canon(warm) != canon(rows):
                    raise AssertionError(f"{tag}.{name}: warm rows differ")
                if ws.h2d_bytes != 0 or ws.kernel_retraces != 0:
                    raise AssertionError(
                        f"{tag}.{name}: warm h2d_bytes={ws.h2d_bytes} "
                        f"kernel_retraces={ws.kernel_retraces}")
                log(f"query {tag}.{name}: rows={len(rows)} "
                    f"dispatches={st.kernel_dispatches} "
                    f"spmd={st.spmd_dispatches} cold_h2d={st.h2d_bytes} "
                    f"warm_h2d={ws.h2d_bytes} "
                    f"warm_retraces={ws.kernel_retraces} "
                    f"cold_s={cold_s:.3f} warm_s={warm_s:.3f}")
            got[name] = rows
        return got

    def crash_recover(self, plans: dict) -> None:
        from repro.storage.query import run_query
        self.ds["MugshotMessages"].crash_and_recover()
        for name in ("index_chain_select", "fused_filter_aggregate"):
            plan, ref = plans[name]
            rows, ex = run_query(plan, self.ds, vectorize=True)
            want = run_query(plan, self.ds)[0] if ref == "row" else ref()
            if canon(rows) != canon(want) or ex.stats.fallback_reasons:
                raise AssertionError(f"after recovery {name} differs")
            log(f"query recovered.{name}: rows={len(rows)}")

    def serve(self, plans: dict) -> None:
        """ServeHarness over a fresh MugshotMessages dataset with the
        dataverse's layout (partitions, flush threshold, btree indexes)
        and a tiered merge policy: two ingest lanes feed the generated
        messages (``message-id`` == pk, so lane-strided pks are exactly
        the generated records) through flushes and merges while two
        query workers alternate snapshot checks with columnar queries."""
        from repro.core.lsm import TieredMergePolicy
        from repro.serve import ServeHarness
        from repro.storage.dataset import PartitionedDataset
        from repro.storage.query import run_query
        mt = self.ds["MugshotMessages"].dtype
        ds = PartitionedDataset("MugshotMessages", mt, "message-id",
                                N_PARTITIONS, self._flush_at(self.n_messages),
                                merge_policy=TieredMergePolicy(k=2))
        ds.create_index("timestamp")
        ds.create_index("author-id")
        # about two and a half flushes per partition: flush, flush, merge
        per_lane = (self.n_messages // 4 + self.n_messages // 16) // 2
        msgs = self.msgs
        served = [plans[k][0] for k in ("index_chain_select",
                                        "fused_filter_aggregate",
                                        "scan_range_select")]
        h = ServeHarness(ds, n_ingest=2, n_query=2, pump_batch=1024,
                         records_per_lane=per_lane,
                         make_record=lambda pk: dict(msgs[pk]),
                         query_plan=lambda salt: served[salt % len(served)],
                         vectorize=True)
        rep = h.run(duration_s=600.0)
        if rep.torn_reads or rep.lost_acks or rep.lost_acked_final:
            raise AssertionError(f"serve: torn={rep.torn_reads} "
                                 f"lost={rep.lost_acks}+"
                                 f"{rep.lost_acked_final}")
        if rep.query_errors:
            raise AssertionError(f"serve: {rep.query_errors[:3]}")
        if rep.ingest_acked < 2 * per_lane or rep.queries == 0:
            raise AssertionError(f"serve: acked={rep.ingest_acked} "
                                 f"queries={rep.queries}")
        merges = sum(p.primary.stats.get("merges", 0) for p in ds.partitions)
        plan = plans["index_chain_select"][0]
        rows, ex = run_query(plan, {"MugshotMessages": ds}, vectorize=True)
        want = run_query(plan, {"MugshotMessages": ds})[0]
        if canon(rows) != canon(want) or ex.stats.fallback_reasons:
            raise AssertionError("serve: final index chain differs")
        log(f"serve: acked={rep.ingest_acked} queries={rep.queries} "
            f"merges={merges} torn=0 lost=0 errors=0 "
            f"ingest_rate={rep.ingest_rate:.1f}/s "
            f"query_p50_ms={rep.query_p50_ms} query_p99_ms={rep.query_p99_ms}")


def canon(rows) -> list:
    return sorted(repr(sorted(r.items(), key=lambda kv: kv[0]))
                  for r in rows)


def check_pallas_ran() -> None:
    """Every Pallas kernel the store keeps ran on the chip at least once
    (its answers were checked with the query that ran it)."""
    from repro import obs
    snap = obs.snapshot()
    idle = [k for k in PALLAS_KERNELS
            if not snap.get(f"kernel.{k}.pallas_dispatches", 0)]
    if idle:
        raise AssertionError(f"Pallas kernels that never ran: {idle}")


def report_device(jax) -> None:
    from repro import obs
    from repro.kernels import device_pool
    snap = obs.snapshot()
    kernels = sorted({k.split(".")[1] for k in snap
                      if k.startswith("kernel.") and k.count(".") == 2
                      and k.endswith(".dispatches")})
    for k in kernels:
        total = int(snap.get(f"kernel.{k}.dispatches", 0))
        pallas = int(snap.get(f"kernel.{k}.pallas_dispatches", 0))
        log(f"kernel {k}: pallas={pallas} xla={total - pallas}")
    log(f"buffer_pool.resident_bytes={device_pool.pool.resident_bytes()}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def run(chips: int, n_messages: int = N_MESSAGES,
        n_users: int = N_USERS) -> None:
    """The smoke proper, on whatever devices JAX holds (``main`` has
    already refused anything but a TPU)."""
    import jax
    from repro.runtime.compile_cache import CompileMeter, enable_compile_cache
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    devs = jax.devices()
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} compile_cache={cache_dir}")
    s = Smoke(n_messages, n_users, meter)
    with s.phase("build"):
        s.build()
    plans = s.plans()
    if chips == 1:
        s.check_queries("chip1", plans)
        check_pallas_ran()
        with s.phase("crash_and_recover"):
            s.crash_recover(plans)
        with s.phase("serve"):
            s.serve(plans)
    else:
        mesh_plans = {k: plans[k] for k in ("index_chain_select",
                                            "scan_range_select",
                                            "fused_filter_aggregate")}
        loop = s.check_queries("loop", mesh_plans)
        mesh = s.check_queries(f"mesh{chips}", mesh_plans, mesh=chips)
        for name in mesh_plans:
            if mesh[name] != loop[name]:
                raise AssertionError(f"mesh{chips}.{name}: rows differ "
                                     f"from the partition loop")
        from repro.kernels import device_pool
        spans = device_pool.pool.placed_device_counts()
        if not spans or max(spans) != chips:
            raise AssertionError(f"sharded operands span {sorted(set(spans))}"
                                 f" devices, wanted {chips}")
        log(f"mesh{chips}: loop and mesh rows identical; "
            f"{len(spans)} sharded operands span {max(spans)} devices")
    hits, misses, comp = meter.snapshot()
    log(f"compile: total_s={comp:.3f} cache_hits={hits} "
        f"cache_misses={misses}")
    report_device(jax)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the partition-loop vs mesh=4 phase")
    args = ap.parse_args()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devs[0].platform})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    run(args.chips)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
