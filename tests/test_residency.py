"""Device buffer pool + fused plan cache: upload-once residency (a
repeated Figure-6 chain ships zero host->device bytes), plan-shape key
correctness (pow2 bucket / dtype / op sequence each key a distinct
entry, repeats never retrace), deterministic eviction on LSM component
retirement under snapshot pins, differential fused-vs-per-operator
equivalence, and no-leak under the serve-harness flush/merge/crash
stress."""

import gc
import threading

import numpy as np
import pytest

from repro import obs
from repro.columnar import plancache as PC
from repro.core import adm
from repro.core import algebra as A
from repro.core.lsm import TieredMergePolicy
from repro.kernels import device_pool as DP
from repro.storage.dataset import PartitionedDataset
from repro.storage.query import run_query


@pytest.fixture(autouse=True)
def _fused_enabled():
    """Every test starts with the fused path enabled (a test that turns
    it off must not leak the switch into the rest of the suite) and with
    no plan shape seen, whatever ran earlier in this process."""
    PC.set_enabled(True)
    PC.plan_cache.clear()
    yield
    PC.set_enabled(True)


def _rec_type():
    return adm.RecordType("ResT", (
        adm.Field("id", adm.INT64),
        adm.Field("a", adm.INT64),
        adm.Field("b", adm.INT64),
        adm.Field("x", adm.DOUBLE),
    ), open=True)


def _dataset(n=120, parts=2, *, index_b=False, threshold=32):
    ds = PartitionedDataset("D", _rec_type(), "id", num_partitions=parts,
                            flush_threshold=threshold,
                            merge_policy=TieredMergePolicy(k=99))
    ds.create_index("a")
    if index_b:
        ds.create_index("b")
    for i in range(n):
        ds.insert({"id": i, "a": i % 50, "b": (i * 7) % 40,
                   "x": float(i) * 0.5,
                   "o": f"s{i}" if i % 3 else i})
    return ds


def _select_plan(lo=10, hi=29):
    return A.select(A.scan("D"), pred=lambda r: lo <= r["a"] <= hi,
                    fields=["a"], ranges={"a": (lo, hi)}, ranges_exact=True)


def _agg_plan():
    return A.aggregate(_select_plan(), {"c": ("count", "*"),
                                        "s": ("sum", "a")})


# ---------------------------------------------------------------------------
# upload-once residency
# ---------------------------------------------------------------------------

def test_repeated_chain_hits_pool_and_ships_nothing():
    ds = _dataset()
    _, ex1 = run_query(_select_plan(), {"D": ds}, vectorize=True)
    assert ex1.stats.rows_fallback == 0
    assert ex1.stats.plan_cache_misses >= 1       # first sighting compiles
    assert ex1.stats.h2d_bytes > 0                # cold: operands upload
    r1 = DP.pool.resident_bytes()
    assert r1 > 0
    s0 = DP.pool.stats()
    _, ex2 = run_query(_select_plan(), {"D": ds}, vectorize=True)
    s1 = DP.pool.stats()
    # warm: every operand already device-resident, plan shape cached
    assert ex2.stats.h2d_bytes == 0
    assert ex2.stats.kernel_retraces == 0
    assert ex2.stats.plan_cache_hits >= 1
    assert ex2.stats.plan_cache_misses == 0
    assert s1["hits"] > s0["hits"]
    assert s1["misses"] == s0["misses"]           # no new uploads
    assert DP.pool.resident_bytes() == r1         # and no growth


def test_warm_aggregate_chain_ships_nothing():
    ds = _dataset()
    rows1, _ = run_query(_agg_plan(), {"D": ds}, vectorize=True)
    rows2, ex2 = run_query(_agg_plan(), {"D": ds}, vectorize=True)
    assert rows1 == rows2
    assert rows1[0]["c"] == sum(1 for i in range(120) if 10 <= i % 50 <= 29)
    assert ex2.stats.h2d_bytes == 0
    assert ex2.stats.kernel_retraces == 0
    assert ex2.stats.plan_cache_hits >= 1


# ---------------------------------------------------------------------------
# plan-shape keys
# ---------------------------------------------------------------------------

def test_plan_keys_split_on_ops_buckets_and_dtypes():
    ds = _dataset()
    # the key set is process-global; start from a clean slate so the
    # entry-count deltas below are deterministic under any test order
    PC.plan_cache.clear()
    run_query(_select_plan(), {"D": ds}, vectorize=True)
    e0 = PC.plan_cache.entry_count()
    # repeat: same shapes, no new entry, no retrace
    _, ex = run_query(_select_plan(), {"D": ds}, vectorize=True)
    assert PC.plan_cache.entry_count() == e0
    assert ex.stats.kernel_retraces == 0
    # different op sequence (chain under LOCAL_AGG) -> new entries
    run_query(_agg_plan(), {"D": ds}, vectorize=True)
    e1 = PC.plan_cache.entry_count()
    assert e1 > e0
    # different pow2 bucket (4x the rows) -> new entries
    big = _dataset(n=600)
    run_query(_select_plan(), {"D": big}, vectorize=True)
    e2 = PC.plan_cache.entry_count()
    assert e2 > e1
    # different validate dtype (f64 vs i64 residual range) -> new entries
    def with_range(fld, lo, hi):
        return A.select(
            A.scan("D"),
            pred=lambda r: 10 <= r["a"] <= 29 and lo <= r[fld] <= hi,
            fields=["a", fld],
            ranges={"a": (10, 29), fld: (lo, hi)}, ranges_exact=True)
    run_query(with_range("b", 0, 20), {"D": ds}, vectorize=True)
    e3 = PC.plan_cache.entry_count()
    assert e3 > e2
    run_query(with_range("x", 0.0, 20.0), {"D": ds}, vectorize=True)
    assert PC.plan_cache.entry_count() > e3


# ---------------------------------------------------------------------------
# eviction: component retirement frees device buffers once pins drop
# ---------------------------------------------------------------------------

def test_merge_retirement_frees_buffers_after_unpin():
    ds = _dataset(n=64, parts=1)
    run_query(_select_plan(), {"D": ds}, vectorize=True)
    _, ex = run_query(_select_plan(), {"D": ds}, vectorize=True)
    assert ex.stats.h2d_bytes == 0                # warm before the merge
    r1 = DP.pool.resident_bytes()
    assert r1 > 0
    prim = ds.partitions[0].primary
    old = [c for c in prim.components if c.valid]
    assert len(old) == 2
    snap = ds.pin()
    prim.merge(old)
    # replaced components are deferred while pinned: buffers stay put
    assert all(not c.retired for c in old)
    assert DP.pool.resident_bytes() == r1
    e0 = DP.pool.stats()["evictions"]
    snap.release()
    # pin count hit zero -> deferred retirement ran -> buffers freed
    assert all(c.retired for c in old)
    assert DP.pool.stats()["evictions"] > e0
    assert DP.pool.resident_bytes() < r1
    # post-merge queries re-warm against the merged component
    rows1, ex1 = run_query(_select_plan(), {"D": ds}, vectorize=True)
    assert ex1.stats.h2d_bytes > 0
    rows2, ex2 = run_query(_select_plan(), {"D": ds}, vectorize=True)
    assert rows1 == rows2
    assert ex2.stats.h2d_bytes == 0


# ---------------------------------------------------------------------------
# differential: fused chain == per-operator chain == row engine
# ---------------------------------------------------------------------------

def _norm(rows):
    return sorted(tuple(sorted(r.items())) for r in rows)


def _diff_plans():
    yield "exact", _select_plan()
    yield "residual", A.select(
        A.scan("D"),
        pred=lambda r: 10 <= r["a"] <= 29 and r["id"] % 3 == 0,
        fields=["a", "id"], ranges={"a": (10, 29)}, ranges_exact=False)
    yield "conjunction", A.select(
        A.scan("D"),
        pred=lambda r: 10 <= r["a"] <= 29 and 5 <= r["b"] <= 30,
        fields=["a", "b"], ranges={"a": (10, 29), "b": (5, 30)},
        ranges_exact=True)
    yield "aggregate", A.aggregate(
        A.select(A.scan("D"), pred=lambda r: 10 <= r["a"] <= 29,
                 fields=["a"], ranges={"a": (10, 29)}, ranges_exact=True),
        {"c": ("count", "*"), "s": ("sum", "a"), "mb": ("min", "b"),
         "Mx": ("max", "x"), "av": ("avg", "x"), "co": ("count", "o")})


def test_fused_chain_matches_unfused_and_row_engine():
    ds = _dataset(index_b=True)
    for name, plan in _diff_plans():
        rows_row, _ = run_query(plan, {"D": ds}, vectorize=False)
        PC.set_enabled(False)
        rows_leg, ex_leg = run_query(plan, {"D": ds}, vectorize=True)
        PC.set_enabled(True)
        rows_fus, ex_fus = run_query(plan, {"D": ds}, vectorize=True)
        # the fused dispatch actually ran (and the disabled run didn't)
        assert ex_fus.stats.plan_cache_hits \
            + ex_fus.stats.plan_cache_misses >= 1, name
        assert ex_leg.stats.plan_cache_hits \
            + ex_leg.stats.plan_cache_misses == 0, name
        assert rows_fus == rows_leg, name         # bit-identical, same order
        assert _norm(rows_fus) == _norm(rows_row), name


# ---------------------------------------------------------------------------
# serve-harness stress: no device-buffer leak across flush/merge/crash
# ---------------------------------------------------------------------------

def test_no_buffer_leak_under_serve_stress():
    from repro.serve import ServeHarness
    rt = adm.RecordType("R", (adm.Field("pk", adm.INT64),
                              adm.Field("val", adm.INT64)), open=True)
    ds = PartitionedDataset("S", rt, "pk", num_partitions=2,
                            flush_threshold=48,
                            merge_policy=TieredMergePolicy(k=3))
    ds.create_index("val")
    plan = lambda: A.select(A.scan("S"),  # noqa: E731
                            pred=lambda r: 1000 <= r["val"] <= 60000,
                            fields=["val"],
                            ranges={"val": (1000, 60000)}, ranges_exact=True)
    h = ServeHarness(ds, n_ingest=2, n_query=1, pump_batch=32,
                     records_per_lane=300)
    gc.collect()
    base = DP.pool.resident_bytes()   # buffers earlier tests keep alive
    pc0 = PC.totals()
    stop = threading.Event()
    fused_queries = [0]

    def chase():
        # fused chains racing the ingest/flush/merge/crash churn: each
        # query pins a snapshot, so retirement defers under its feet
        while not stop.is_set():
            try:
                run_query(plan(), {"S": ds}, vectorize=True, snapshot=True)
                fused_queries[0] += 1
            except Exception:      # noqa: BLE001  (crash window races)
                pass

    thr = threading.Thread(target=chase, daemon=True)
    thr.start()
    rep = h.run(duration_s=12.0, checkpoint_after=150, crash_after=300)
    stop.set()
    thr.join(timeout=10.0)
    assert fused_queries[0] > 0
    pc1 = PC.totals()
    assert (pc1[0] + pc1[1]) > (pc0[0] + pc0[1])  # fused path exercised
    assert rep.recoveries >= 1                    # the crash really happened
    peak = DP.pool.resident_bytes()
    assert peak > 0
    # teardown: dataset, harness and caches die -> finalizers must evict
    # every pooled buffer (no entry may outlive its host array)
    del h, ds, rep
    gc.collect()
    gc.collect()
    leftover = DP.pool.resident_bytes()
    # residency must fall back to (near) the pre-stress baseline: the
    # lru'd no-predicate liveness rows (kernels.columnar_ops._live_pred)
    # legitimately persist, but the stress dataset's component/batch
    # buffers — many MB of flush/merge/crash churn — must all be gone
    assert leftover <= base + (1 << 20), (base, leftover, peak)
    assert leftover <= 8 << 20, (base, leftover, peak)
