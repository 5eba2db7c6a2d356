"""Columnar engine: ADM <-> ColumnBatch round-trips, kernel oracles, the
columnar LSM scan, and row-vs-columnar executor equality on every
tinysocial query shape."""

import datetime as dt
import random

import numpy as np
import pytest

from repro import obs
from repro.columnar.batch import ColumnBatch
from repro.columnar.schema import ColumnSchema
from repro.configs.tinysocial import build_dataverse, message_type, user_type
from repro.core import algebra as A
from repro.core.rewriter import RewriteConfig
from repro.kernels import columnar_ops as K
from repro.storage.query import run_query

LO, HI = dt.datetime(2010, 1, 1), dt.datetime(2011, 6, 30)
MLO = dt.datetime(2014, 3, 1)


@pytest.fixture(scope="module")
def tiny():
    _, ds = build_dataverse(num_users=120, num_messages=600,
                            num_partitions=4, flush_threshold=64)
    return ds


def _canon(rows):
    return sorted(repr(sorted(r.items(), key=lambda kv: kv[0]))
                  for r in rows)


# ---------------------------------------------------------------------------
# round-trips
# ---------------------------------------------------------------------------

def test_closed_type_roundtrip():
    mt = message_type()
    rows = [mt.validate({
        "message-id": i, "author-id": i % 7,
        "timestamp": dt.datetime(2014, 1, 1 + i, 2, 3, 4, 500000 + i),
        "sender-location": (33.5, -117.5),
        "tags": ["a", "b"], "message": f"msg {i}",
        **({"in-response-to": i - 1} if i % 2 else {}),
    }) for i in range(1, 20)]
    back = ColumnBatch.from_rows(rows).to_rows()
    assert back == rows


def test_open_type_roundtrip_missing_null_and_dict():
    ut = user_type()
    rows = [
        ut.validate({"id": 1, "alias": "a", "name": "A", "user-since": LO,
                     "address": {"street": "1", "city": "i", "state": "CA",
                                 "zip": "1", "country": "USA"},
                     "friend-ids": [2], "employment": [],
                     "job-kind": "part-time"}),       # open string field
        ut.validate({"id": 2, "alias": "b", "name": "B", "user-since": HI,
                     "address": {"street": "2", "city": "i", "state": "WA",
                                 "zip": "2", "country": "USA"},
                     "friend-ids": [], "employment": [],
                     "nerd-score": 11}),              # open int field
        ut.validate({"id": 3, "alias": "c", "name": "C", "user-since": LO,
                     "address": {"street": "3", "city": "i", "state": "OR",
                                 "zip": "3", "country": "USA"},
                     "friend-ids": [], "employment": [],
                     "nickname": None}),              # present-but-null
    ]
    batch = ColumnBatch.from_rows(rows)
    back = batch.to_rows()
    assert back == rows
    # missing open fields stay missing, null stays null
    assert "job-kind" not in back[1] and back[2]["nickname"] is None
    # string dictionary is sorted => code order == lexicographic order
    col = batch.columns["alias"]
    assert col.values == ["a", "b", "c"]
    assert col.data.tolist() == [0, 1, 2]


def test_seeded_random_open_roundtrip():
    rng = random.Random(7)
    pool = {
        "i": lambda: rng.randrange(-2**40, 2**40),
        "f": lambda: rng.uniform(-1e6, 1e6),
        "s": lambda: "".join(rng.choice("abcé-19 ")
                             for _ in range(rng.randrange(9))),
        "b": lambda: rng.random() < 0.5,
        "t": lambda: dt.datetime(2000 + rng.randrange(30), 1 + rng.randrange(12),
                                 1 + rng.randrange(28), rng.randrange(24),
                                 rng.randrange(60), rng.randrange(60),
                                 rng.randrange(10**6)),
        "d": lambda: dt.date(1960 + rng.randrange(100), 1 + rng.randrange(12),
                             1 + rng.randrange(28)),
        "l": lambda: [rng.randrange(10) for _ in range(rng.randrange(4))],
        "n": lambda: None,
    }
    for _ in range(30):
        rows = []
        fields = rng.sample(sorted(pool), rng.randrange(2, 6))
        for i in range(rng.randrange(1, 30)):
            r = {"id": i}
            for f in fields:
                if rng.random() < 0.8:
                    r[f] = pool[f]()
            rows.append(r)
        assert ColumnBatch.from_rows(rows).to_rows() == rows


def test_all_missing_str_column_decodes():
    """Regression (found by the differential harness): a str column that
    is entirely missing has an empty dictionary but zero-filled codes;
    decode must not index the empty dictionary."""
    s = ColumnSchema({"id": "i64", "txt": "str"})
    rows = [{"id": 1}, {"id": 2}]
    batch = ColumnBatch.from_rows(rows, s)
    assert batch.to_rows() == rows
    assert batch.columns["txt"].values == []


def test_concat_unions_schemas_and_dictionaries():
    b1 = ColumnBatch.from_rows([{"id": 1, "s": "zz"}, {"id": 2, "s": "aa"}])
    b2 = ColumnBatch.from_rows([{"id": 3, "x": 1.5}, {"id": 4, "s": "mm"}])
    cat = ColumnBatch.concat([b1, b2])
    assert cat.to_rows() == [{"id": 1, "s": "zz"}, {"id": 2, "s": "aa"},
                             {"id": 3, "x": 1.5}, {"id": 4, "s": "mm"}]
    assert cat.columns["s"].values == ["aa", "mm", "zz"]


# ---------------------------------------------------------------------------
# kernels: jnp fallback vs pallas (interpret) vs numpy oracle
# ---------------------------------------------------------------------------

def test_kernel_range_mask_and_fused_aggregate(rng):
    n = 777
    x = rng.integers(-10**6, 10**6, n)
    xv = rng.random(n) < 0.9
    y = rng.normal(size=n)
    yv = rng.random(n) < 0.8
    preds = [(x, xv, -500000, 400000)]
    oracle = xv & (x >= -500000) & (x <= 400000)
    assert np.array_equal(K.range_mask(preds, n), oracle)
    assert np.array_equal(
        K.range_mask(preds, n, force_pallas=True, interpret=True), oracle)

    res = K.fused_filter_aggregate(preds, [(x, xv), (y, yv)], n)
    assert res["count"] == int(oracle.sum())
    assert res["sums"][0] == int(x[oracle].sum())
    assert res["mins"][0] == int(x[oracle].min())
    assert res["maxs"][0] == int(x[oracle].max())
    ok_y = oracle & yv
    assert res["cnts"][1] == int(ok_y.sum())
    assert res["sums"][1] == pytest.approx(float(y[ok_y].sum()))

    # the Pallas kernel (interpret mode off-TPU) takes the int32-exact
    # columns and agrees exactly; the float column keeps the whole call
    # on the exact x64 core
    before = obs.snapshot().get(
        "kernel.fused_filter_aggregate.pallas_dispatches", 0)
    rp = K.fused_filter_aggregate(preds, [(x, xv)], n,
                                  force_pallas=True, interpret=True)
    assert obs.snapshot()[
        "kernel.fused_filter_aggregate.pallas_dispatches"] == before + 1
    assert rp["count"] == res["count"]
    assert (rp["sums"][0], rp["mins"][0], rp["maxs"][0], rp["cnts"][0]) \
        == (res["sums"][0], res["mins"][0], res["maxs"][0], res["cnts"][0])
    rm = K.fused_filter_aggregate(preds, [(x, xv), (y, yv)], n,
                                  force_pallas=True, interpret=True)
    assert rm == res

    # int32 values whose absolute sum does not fit int32: count/min/max
    # alone still take the kernel, a read sum keeps the exact core
    big = rng.integers(2**29, 2**30, n)
    ok_b = oracle & xv
    for summed, on_kernel in (([False], 1), ([True], 0)):
        before = obs.snapshot().get(
            "kernel.fused_filter_aggregate.pallas_dispatches", 0)
        rb = K.fused_filter_aggregate(preds, [(big, xv)], n, summed=summed,
                                      force_pallas=True, interpret=True)
        assert obs.snapshot().get(
            "kernel.fused_filter_aggregate.pallas_dispatches", 0) \
            == before + on_kernel
        assert (rb["count"], rb["mins"][0], rb["maxs"][0], rb["cnts"][0]) \
            == (int(oracle.sum()), int(big[ok_b].min()),
                int(big[ok_b].max()), int(ok_b.sum()))
        if summed[0]:
            assert rb["sums"][0] == int(big[ok_b].sum())

    # unbounded sides and empty results
    assert K.range_mask([(x, xv, None, None)], n).sum() == xv.sum()
    empty = K.fused_filter_aggregate([(x, xv, 10**7, None)], [(x, xv)], n)
    assert empty["count"] == 0 and empty["mins"] == [None]


# ---------------------------------------------------------------------------
# columnar LSM scan
# ---------------------------------------------------------------------------

def test_scan_partition_batch_matches_row_scan(tiny):
    users = tiny["MugshotUsers"]
    for i in range(users.num_partitions):
        rows = users.scan_partition(i)
        crows = users.scan_partition_batch(i).to_rows()
        assert crows == rows


def test_scan_batch_sees_updates_deletes_and_tombstones():
    _, ds = build_dataverse(num_users=50, num_messages=10,
                            num_partitions=2, flush_threshold=8)
    users = ds["MugshotUsers"]
    base = {"alias": "x", "name": "X", "user-since": LO,
            "address": {"street": "1", "city": "i", "state": "CA",
                        "zip": "1", "country": "USA"},
            "friend-ids": [], "employment": []}
    users.delete(7)
    users.insert({"id": 11, **base, "name": "Updated"})   # overwrite
    users.insert({"id": 1007, **base, "extra-open": 42})  # new open field
    got = []
    for i in range(users.num_partitions):
        got.extend(users.scan_partition_batch(i).to_rows())
    want = users.scan()
    assert _canon(got) == _canon(want)
    ids = {r["id"] for r in got}
    assert 7 not in ids and 1007 in ids
    assert next(r for r in got if r["id"] == 11)["name"] == "Updated"


def test_scan_projection_and_component_storage(tiny):
    msgs = tiny["MugshotMessages"]
    b = msgs.scan_partition_batch(0, ["message-id", "timestamp"])
    assert set(b.columns) == {"message-id", "timestamp"}
    comp = next(c for c in msgs.partitions[0].primary.components if c.valid)
    # columnar-native storage: the flush shredded the component's batch
    # as primary data (tombstone bitmap included) and no row-dict view
    # was ever forced — projected scans are zero-copy dict subsets
    assert comp.batch is not None and "timestamp" in comp.batch.columns
    assert comp.tomb is not None and comp._rows is None
    again = msgs.scan_partition_batch(0, ["message-id", "timestamp"])
    assert again.to_rows() == b.to_rows()


# ---------------------------------------------------------------------------
# executor equality: every tinysocial query shape, row vs columnar
# ---------------------------------------------------------------------------

def _plans():
    return {
        "range_select": A.select(
            A.scan("MugshotUsers"),
            pred=lambda r: LO <= r["user-since"] <= HI,
            fields=["user-since"], ranges={"user-since": (LO, HI)}),
        "equijoin": A.join(A.scan("MugshotMessages"),
                           A.scan("MugshotUsers"),
                           ["author-id"], ["id"]),
        "double_select_join": A.join(
            A.select(A.scan("MugshotMessages"),
                     pred=lambda r: r["timestamp"] >= MLO,
                     fields=["timestamp"],
                     ranges={"timestamp": (MLO, dt.datetime(2015, 1, 1))}),
            A.select(A.scan("MugshotUsers"),
                     pred=lambda r: LO <= r["user-since"] <= HI,
                     fields=["user-since"],
                     ranges={"user-since": (LO, HI)}),
            ["author-id"], ["id"]),
        "grouped_agg_topk": A.limit(A.order_by(
            A.group_by(A.scan("MugshotMessages"), ["author-id"],
                       {"cnt": ("count", "*")}), ["cnt"], desc=True), 5),
        "avg_agg": A.aggregate(A.scan("MugshotMessages"),
                               {"alen": ("avg", "message-id")}),
        "sum_min_max": A.aggregate(
            A.scan("MugshotMessages"),
            {"s": ("sum", "author-id"), "mn": ("min", "timestamp"),
             "mx": ("max", "timestamp"), "c": ("count", "timestamp")}),
        "fused_exact_agg": A.aggregate(
            A.select(A.scan("MugshotMessages"),
                     pred=lambda r: r["timestamp"] >= MLO,
                     fields=["timestamp"],
                     ranges={"timestamp": (MLO, dt.datetime(2030, 1, 1))},
                     ranges_exact=True, hints=["skip-index"]),
            {"c": ("count", "*"), "am": ("avg", "author-id")}),
        "group_over_join": A.group_by(
            A.join(A.scan("MugshotMessages"), A.scan("MugshotUsers"),
                   ["author-id"], ["id"]),
            ["author-id"],
            {"mn": ("min", "timestamp"), "c": ("count", "*")}),
        "project_orderby_limit": A.limit(A.order_by(
            A.project(A.scan("MugshotUsers"), ["id", "name"]),
            ["id"], desc=True), 7),
    }


@pytest.mark.parametrize("shape", sorted(_plans()))
@pytest.mark.parametrize("cfg", ["default", "noidx", "nosplit", "nopush"])
def test_vectorize_identical_to_row_engine(tiny, shape, cfg):
    config = {
        "default": RewriteConfig(),
        "noidx": RewriteConfig(use_indexes=False),
        "nosplit": RewriteConfig(split_aggregation=False),
        "nopush": RewriteConfig(push_limit_into_sort=False),
    }[cfg]
    plan = _plans()[shape]
    rows_r, _ = run_query(plan, tiny, config=config)
    rows_c, _ = run_query(plan, tiny, config=config, vectorize=True)
    assert _canon(rows_r) == _canon(rows_c)


def test_vectorized_stats_recorded(tiny):
    plan = A.aggregate(
        A.select(A.scan("MugshotMessages"),
                 pred=lambda r: r["timestamp"] >= MLO,
                 fields=["timestamp"],
                 ranges={"timestamp": (MLO, dt.datetime(2030, 1, 1))},
                 ranges_exact=True, hints=["skip-index"]),
        {"c": ("count", "*")})
    rows, ex = run_query(plan, tiny, vectorize=True)
    assert ex.stats.rows_vectorized > 0
    assert ex.stats.rows_fallback == 0
    # op cardinalities keep the row engine's accounting
    assert ex.stats.op_rows["DATASET_SCAN"] == 600
    assert ex.stats.op_rows["STREAM_SELECT"] == rows[0]["c"]

    # index access paths vectorize too: candidate PKs -> position bitmaps
    plan_ix = A.select(A.scan("MugshotUsers"),
                       pred=lambda r: LO <= r["user-since"] <= HI,
                       fields=["user-since"],
                       ranges={"user-since": (LO, HI)})
    rows_ix, ex2 = run_query(plan_ix, tiny, vectorize=True)
    assert ex2.stats.rows_fallback == 0
    assert ex2.stats.rows_index_vectorized > 0
    assert ex2.stats.op_rows["POST_VALIDATE_SELECT"] == len(rows_ix)
    # every index-path op keeps the row engine's accounting keys
    assert ex2.stats.op_rows["SECONDARY_INDEX_SEARCH"] >= len(rows_ix)
    assert ex2.stats.op_rows["SORT_PK"] == \
        ex2.stats.op_rows["SECONDARY_INDEX_SEARCH"]


def test_min_on_object_column_matches_row_engine(tiny):
    """min/max over a non-summable obj column (lists) must not touch
    sum()."""
    plan = A.aggregate(A.scan("MugshotMessages"), {"mn": ("min", "tags")})
    rows_r, _ = run_query(plan, tiny)
    rows_c, _ = run_query(plan, tiny, vectorize=True)
    assert rows_r == rows_c


def test_explicit_null_survives_downstream_operators():
    """Empty-group aggregates surface as explicit None through project
    and at the row boundary, like the row engine."""
    _, ds = build_dataverse(num_users=60, num_messages=10,
                            num_partitions=2, flush_threshold=16)
    users = ds["MugshotUsers"]
    users.insert({"id": 1060, "alias": "n", "name": "N", "user-since": LO,
                  "address": {"street": "1", "city": "i", "state": "CA",
                              "zip": "1", "country": "USA"},
                  "friend-ids": [], "employment": [], "nerd-score": 9})
    plan = A.project(
        A.group_by(A.scan("MugshotUsers"), ["id"],
                   {"m": ("min", "nerd-score")}), ["id", "m"])
    rows_r, _ = run_query(plan, ds)
    rows_c, _ = run_query(plan, ds, vectorize=True)
    assert _canon(rows_r) == _canon(rows_c)
    assert {"id": 0, "m": None} in rows_c     # None, not a missing key


# ---------------------------------------------------------------------------
# index access path: intersection kernel + short-circuits
# ---------------------------------------------------------------------------

def test_sorted_intersect_mask_matches_oracle(rng):
    keys = np.unique(rng.integers(0, 2 ** 20, 4000))
    cands = np.unique(np.concatenate([
        rng.choice(keys, min(300, len(keys)), replace=False),
        rng.integers(0, 2 ** 20, 100)]))
    oracle = np.isin(keys, cands)
    assert np.array_equal(K.sorted_intersect_mask(keys, cands), oracle)
    # the Pallas membership kernel (interpret off-TPU) agrees exactly on
    # f32-exact int domains
    assert np.array_equal(
        K.sorted_intersect_mask(keys, cands, force_pallas=True,
                                interpret=True), oracle)
    # zero-length guards: no kernel launch on either empty side
    assert K.sorted_intersect_mask(keys[:0], cands).shape == (0,)
    assert not K.sorted_intersect_mask(keys, cands[:0]).any()
    # pks beyond f32-exact range stay on the exact x64 oracle
    big = np.asarray([2 ** 40, 2 ** 40 + 1, 2 ** 40 + 2], dtype=np.int64)
    got = K.sorted_intersect_mask(big, big[1:2])
    assert got.tolist() == [False, True, False]


def test_partition_pk_array_aligned_with_scan(tiny):
    users = tiny["MugshotUsers"]
    for i in range(users.num_partitions):
        keys = users.partition_pk_array(i).tolist()
        rows = users.scan_partition_batch(i).to_rows()
        assert keys == [r["id"] for r in rows]
        assert keys == sorted(keys)


def test_empty_candidate_set_short_circuits(tiny):
    """Index range matching nothing -> empty batches end-to-end: count 0,
    avg/min as explicit None (no NaN), nothing on the row engine."""
    future = (dt.datetime(2031, 1, 1), dt.datetime(2032, 1, 1))
    plan = A.aggregate(
        A.select(A.scan("MugshotUsers"),
                 pred=lambda r: future[0] <= r["user-since"] <= future[1],
                 fields=["user-since"], ranges={"user-since": future}),
        {"c": ("count", "*"), "m": ("avg", "id"), "mn": ("min", "id")})
    rows_r, _ = run_query(plan, tiny)
    rows_c, ex = run_query(plan, tiny, vectorize=True)
    assert rows_r == rows_c == [{"c": 0, "m": None, "mn": None}]
    assert ex.stats.rows_fallback == 0
    assert ex.stats.op_rows["POST_VALIDATE_SELECT"] == 0


def test_all_deleted_partitions_short_circuit():
    """Every row tombstoned: the index path yields empty ColumnBatches
    (no NaN aggregates, no zero-length kernel launches)."""
    _, ds = build_dataverse(num_users=40, num_messages=10,
                            num_partitions=2, flush_threshold=8)
    users = ds["MugshotUsers"]
    for r in users.scan():
        users.delete(r["id"])
    assert users.scan() == []
    sel = A.select(A.scan("MugshotUsers"),
                   pred=lambda r: r["user-since"] >= LO,
                   fields=["user-since"], ranges={"user-since": (LO, None)})
    rows_r, _ = run_query(sel, ds)
    rows_c, ex = run_query(sel, ds, vectorize=True)
    assert rows_r == rows_c == []
    assert ex.stats.rows_fallback == 0
    agg = A.aggregate(
        A.select(A.scan("MugshotUsers"),
                 pred=lambda r: r["user-since"] >= LO,
                 fields=["user-since"],
                 ranges={"user-since": (LO, None)}),
        {"s": ("sum", "id"), "m": ("avg", "id")})
    rows_ra, _ = run_query(agg, ds)
    rows_ca, _ = run_query(agg, ds, vectorize=True)
    assert rows_ra == rows_ca == [{"s": 0, "m": None}]
    for i in range(users.num_partitions):
        assert len(users.partition_pk_array(i)) == 0
        assert len(users.scan_partition_batch(i)) == 0


def test_schema_inference_unifies_open_fields():
    s = ColumnSchema()
    s.observe_value("x", 1)
    assert s.kind("x") == "i64"
    s.observe_value("x", 2.5)
    assert s.kind("x") == "obj"       # exact: ints must decode as ints
    s.observe_value("y", 2.5)
    s.observe_value("y", 3.5)
    assert s.kind("y") == "f64"
    s.observe_value("y", "oops")
    assert s.kind("y") == "obj"


# ---------------------------------------------------------------------------
# shape-stable kernels: pow2-padded batches never retrace on repeats
# ---------------------------------------------------------------------------

def test_repeated_queries_zero_kernel_retraces(tiny):
    """Component batches and post-index-gather aggregate batches go
    through the shared pow2-padding path, so a repeated query — scan or
    index access -> aggregate — triggers zero new jit traces
    (``ExecStats.kernel_retraces``)."""
    scan_agg = A.aggregate(
        A.select(A.scan("MugshotMessages"),
                 pred=lambda r: r["timestamp"] >= MLO,
                 fields=["timestamp"], ranges={"timestamp": (MLO, None)},
                 ranges_exact=True, hints=["skip-index"]),
        {"c": ("count", "*"), "av": ("avg", "author-id")})
    index_agg = A.aggregate(
        A.select(A.scan("MugshotMessages"),
                 pred=lambda r: r["timestamp"] >= MLO,
                 fields=["timestamp"], ranges={"timestamp": (MLO, None)}),
        {"c": ("count", "*"), "mx": ("max", "message-id")})
    for plan in (scan_agg, index_agg):
        run_query(plan, tiny, vectorize=True)          # warm traces
        _, ex = run_query(plan, tiny, vectorize=True)
        assert ex.stats.kernel_retraces == 0
        _, ex = run_query(plan, tiny, vectorize=True)
        assert ex.stats.kernel_retraces == 0
    assert ex.stats.rows_index_vectorized > 0          # index path ran


def test_column_padded_view_cached_and_invalid():
    b = ColumnBatch.from_rows([{"a": i} for i in range(13)])
    col = b.columns["a"]
    data, valid = col.padded()
    assert data.shape == (16,) and valid.shape == (16,)
    assert not valid[13:].any() and valid[:13].all()
    assert col.padded()[0] is data               # cached, one allocation
    # pow2 lengths pass through untouched
    b2 = ColumnBatch.from_rows([{"a": i} for i in range(8)])
    d2, _ = b2.columns["a"].padded()
    assert d2 is b2.columns["a"].data


# ---------------------------------------------------------------------------
# ColumnBatch as LSM primary storage: sort_by / merge_sorted
# ---------------------------------------------------------------------------

def test_batch_sort_by_and_merge_sorted():
    rows_new = [{"id": 5, "v": "n5"}, {"id": 1, "v": "n1"}]
    rows_old = [{"id": 1, "v": "o1"}, {"id": 2, "v": "o2"},
                {"id": 9, "v": "o9"}]
    bn = ColumnBatch.from_rows(rows_new).sort_by(["id"])
    bo = ColumnBatch.from_rows(rows_old).sort_by(["id"])
    assert [r["id"] for r in bn.to_rows()] == [1, 5]
    merged, keys, tomb = ColumnBatch.merge_sorted(
        [bn, bo], [np.asarray([1, 5]), np.asarray([1, 2, 9])],
        [np.zeros(2, bool), np.zeros(3, bool)])
    assert keys.tolist() == [1, 2, 5, 9] and not tomb.any()
    got = merged.to_rows()
    assert [r["v"] for r in got] == ["n1", "o2", "n5", "o9"]  # newest wins
    # tombstone drop (merge includes the oldest component)
    merged2, keys2, tomb2 = ColumnBatch.merge_sorted(
        [bn, bo], [np.asarray([1, 5]), np.asarray([1, 2, 9])],
        [np.asarray([True, False]), np.zeros(3, bool)],
        drop_tombstones=True)
    assert keys2.tolist() == [2, 5, 9] and not tomb2.any()
    assert [r["v"] for r in merged2.to_rows()] == ["o2", "n5", "o9"]


def test_batch_sort_by_absent_values_sort_first():
    bm = ColumnBatch.from_rows([{"id": 1, "a": 3}, {"id": 2}])
    assert [r["id"] for r in bm.sort_by(["a"]).to_rows()] == [2, 1]
    assert [r["id"] for r in bm.sort_by(["a"], desc=True).to_rows()] == [1, 2]
