"""Row-vs-columnar differential harness.

Property-based generator of random datasets (open-type records, optional
fields, updates, deletes, LSM flush/merge/recovery) + query plans
(including every index access path), asserting that
``Executor(vectorize=True)`` and ``vectorize=False`` produce identical
sorted results.  Runs 320 generated cases under a fixed seed (the
hypothesis shim seeds per test name; real hypothesis runs derandomized),
so ``scripts/verify.sh`` is reproducible in CI.  The lifecycle-schedule
cases additionally interleave explicit flush/merge/crash_and_recover with
queries and assert the columnar-native storage invariant: disk-resident
components keep ColumnBatch + tombstone bitmap as primary data, with the
row view derived lazily and never retained by flush or merge.
"""

import random

import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container has no hypothesis: seeded shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import adm
from repro.core import algebra as A
from repro.core.functions import (edit_distance_check, spatial_distance,
                                  word_tokens)
from repro.core.lsm import LSMIndex, TieredMergePolicy
from repro.storage.dataset import PartitionedDataset
from repro.storage.query import run_query

VOCAB = ["tpu", "jax", "lsm", "tonight", "tonite", "coffee", "fuzzy",
         "mesh", "verona"]


def _canon(rows):
    return sorted(repr(sorted(r.items(), key=lambda kv: kv[0]))
                  for r in rows)


def _record_type() -> adm.RecordType:
    return adm.RecordType("DiffT", (
        adm.Field("id", adm.INT64),
        adm.Field("g", adm.INT64),
        adm.Field("a", adm.INT64, optional=True),
        adm.Field("b", adm.INT64, optional=True),
        adm.Field("txt", adm.STRING, optional=True),
        adm.Field("loc", adm.POINT, optional=True),
    ), open=True)


def _build(rng: random.Random, n_rows: int, parts: int, threshold: int,
           index_kinds=("a", "b", "txt", "loc"), txt_kind="keyword"):
    """Random dataset lifecycle: indexes created before AND after inserts
    (backfill), interleaved updates + deletes, optional crash recovery.
    Leaves memtables unflushed so every LSM read tier is live.
    ``txt_kind`` picks the text index flavor (keyword | ngram)."""
    ds = PartitionedDataset(
        "D", _record_type(), "id", num_partitions=parts,
        flush_threshold=threshold,
        merge_policy=TieredMergePolicy(k=rng.choice([2, 3, 4])))
    late = set()
    if "a" in index_kinds:
        if rng.random() < 0.5:
            ds.create_index("a")
        else:
            late.add("a")
    for fld, kind in (("b", "btree"), ("txt", txt_kind), ("loc", "rtree")):
        if fld in index_kinds:
            if rng.random() < 0.5:
                ds.create_index(fld, kind=kind)
            else:
                late.add(fld)
    key_space = max(2 * n_rows, 4)
    for _ in range(n_rows):
        r = {"id": rng.randrange(key_space), "g": rng.randrange(4)}
        if rng.random() < 0.9:
            r["a"] = rng.randrange(-50, 50)
        if rng.random() < 0.7:
            r["b"] = rng.randrange(0, 30)
        if rng.random() < 0.8:
            r["txt"] = " ".join(rng.choice(VOCAB)
                                for _ in range(rng.randrange(1, 5)))
        if rng.random() < 0.7:
            r["loc"] = (rng.uniform(0.0, 1.0), rng.uniform(0.0, 1.0))
        if rng.random() < 0.5:   # open field of drifting kind
            r["x"] = rng.choice([rng.randrange(100), rng.uniform(0.0, 9.0),
                                 rng.choice(VOCAB)])
        if rng.random() < 0.3:
            r["flag"] = rng.random() < 0.5
        ds.insert(r)
        if rng.random() < 0.1:
            ds.delete(rng.randrange(key_space))
    for fld in ("a", "b"):
        if fld in late:
            ds.create_index(fld)
    if "txt" in late:
        ds.create_index("txt", kind=txt_kind)
    if "loc" in late:
        ds.create_index("loc", kind="rtree")
    for _ in range(rng.randrange(n_rows // 4 + 1)):
        ds.delete(rng.randrange(key_space))
    if rng.random() < 0.3:
        ds.crash_and_recover()
    return ds


def _range_pred(fld, lo, hi):
    return lambda r: fld in r \
        and (lo is None or r[fld] >= lo) and (hi is None or r[fld] <= hi)


def _btree_select(rng):
    lo = rng.randrange(-60, 50)
    hi = lo + rng.randrange(0, 60)
    lo_, hi_ = lo, hi
    if rng.random() < 0.15:
        lo_ = None
    elif rng.random() < 0.15:
        hi_ = None
    hints = ["skip-index"] if rng.random() < 0.25 else []
    return A.select(A.scan("D"), pred=_range_pred("a", lo_, hi_),
                    fields=["a"], ranges={"a": (lo_, hi_)},
                    ranges_exact=rng.random() < 0.5, hints=hints)


def _multi_select(rng):
    lo_a = rng.randrange(-60, 40)
    hi_a = lo_a + rng.randrange(5, 70)
    lo_b = rng.randrange(0, 20)
    hi_b = lo_b + rng.randrange(0, 15)
    pa, pb = _range_pred("a", lo_a, hi_a), _range_pred("b", lo_b, hi_b)
    return A.select(A.scan("D"),
                    pred=lambda r: pa(r) and pb(r), fields=["a", "b"],
                    ranges={"a": (lo_a, hi_a), "b": (lo_b, hi_b)},
                    ranges_exact=rng.random() < 0.5)


def _relational_plan(rng, kind):
    if kind == "btree":
        return _btree_select(rng)
    if kind == "multi":
        return _multi_select(rng)
    if kind == "agg":
        return A.aggregate(_btree_select(rng),
                           {"c": ("count", "*"), "s": ("sum", "a"),
                            "mn": ("min", "b"), "av": ("avg", "b")})
    if kind == "group":
        return A.group_by(_btree_select(rng), ["g"],
                          {"c": ("count", "*"), "mx": ("max", "a")})
    if kind == "topk":
        return A.limit(A.order_by(_btree_select(rng), ["id"],
                                  desc=rng.random() < 0.5),
                       rng.randrange(1, 9))
    if kind == "project":
        return A.project(_btree_select(rng), ["id", "g", "a"])
    raise AssertionError(kind)


def _assert_engines_agree(ds, plan):
    rows_r, _ = run_query(plan, {"D": ds})
    rows_c, ex = run_query(plan, {"D": ds}, vectorize=True)
    assert _canon(rows_r) == _canon(rows_c), \
        f"row={len(rows_r)} col={len(rows_c)}"
    return ex


@given(st.integers(0, 10 ** 9), st.integers(0, 90),
       st.integers(2, 4), st.sampled_from([4, 9, 17, 33]),
       st.sampled_from(["btree", "multi", "agg", "group", "topk",
                        "project"]))
@settings(max_examples=100, deadline=None, derandomize=True)
def test_differential_relational(seed, n_rows, parts, threshold, kind):
    rng = random.Random(seed * 7 + sum(map(ord, kind)))  # hash()-free: stable
    ds = _build(rng, n_rows, parts, threshold, index_kinds=("a", "b"))
    _assert_engines_agree(ds, _relational_plan(rng, kind))


@given(st.integers(0, 10 ** 9), st.integers(0, 70),
       st.integers(2, 4), st.sampled_from([5, 11, 29]))
@settings(max_examples=60, deadline=None, derandomize=True)
def test_differential_spatial(seed, n_rows, parts, threshold):
    rng = random.Random(seed)
    ds = _build(rng, n_rows, parts, threshold, index_kinds=("loc",))
    center = (rng.uniform(0.0, 1.0), rng.uniform(0.0, 1.0))
    radius = rng.uniform(0.02, 0.5)
    plan = A.select(
        A.scan("D"),
        pred=lambda r: "loc" in r
        and spatial_distance(r["loc"], center) <= radius,
        fields=["loc"], spatial=("loc", center, radius))
    _assert_engines_agree(ds, plan)


def _fuzzy_spec(rng, kind):
    base = rng.choice(VOCAB)
    # sometimes corrupt the target so near-misses exercise the DP/bounds
    target = base
    if rng.random() < 0.5 and base:
        j = rng.randrange(len(base))
        target = base[:j] + rng.choice("abxyz") + base[j + 1:]
    if kind == "ed":
        return ("txt", "ed", target, rng.choice([0, 1, 2, 3]))
    if rng.random() < 0.4:           # multi-word target for gram jaccard
        target = target + " " + rng.choice(VOCAB)
    return ("txt", "jaccard", target, rng.choice([0.2, 0.4, 0.6, 0.9]))


@given(st.integers(0, 10 ** 9), st.integers(0, 70),
       st.integers(2, 4), st.sampled_from([5, 11, 29]),
       st.sampled_from(["ed", "jaccard"]))
@settings(max_examples=60, deadline=None, derandomize=True)
def test_differential_fuzzy(seed, n_rows, parts, threshold, kind):
    """Fuzzy selects over an ngram-indexed field: the NGRAM_INDEX_SEARCH
    -> T_OCCURRENCE -> verify chain agrees with the row engine across
    the same flush/merge/recover lifecycles as every other index path
    (_build interleaves them), including memtable-resident rows, deletes,
    open-type drift, and late index creation (component backfill).
    Variants cover the spec's own oracle (kernel-only verify, whether
    the plan declares ``ranges_exact`` or the rewriter infers it), preds
    carrying an extra non-fuzzy conjunct (residual re-check must run),
    and jaccard
    specs whose gram length differs from the index's (no pruning, shared
    verify)."""
    from repro.fuzzy import fuzzy_predicate
    rng = random.Random(seed * 13 + sum(map(ord, kind)))
    ds = _build(rng, n_rows, parts, threshold, index_kinds=("a", "txt"),
                txt_kind="ngram")
    spec = _fuzzy_spec(rng, kind)
    variant = rng.choice(["plain", "exact", "conjunct", "spec_k"])
    if variant == "spec_k" and kind == "jaccard":
        spec = spec + (2,)        # predicate gram length != index's 3
    oracle = fuzzy_predicate(spec)
    if variant == "conjunct":
        lo_g = rng.randrange(0, 3)
        plan = A.select(A.scan("D"),
                        pred=lambda r: oracle(r) and r["g"] >= lo_g,
                        fields=["txt", "g"], fuzzy=spec)
    else:
        plan = A.select(A.scan("D"), pred=oracle, fields=["txt"],
                        fuzzy=spec, ranges_exact=variant == "exact")
    ex = _assert_engines_agree(ds, plan)
    assert ex.stats.rows_fallback == 0


@given(st.integers(0, 10 ** 9), st.integers(0, 70),
       st.integers(2, 4), st.sampled_from([5, 11, 29]),
       st.sampled_from(VOCAB), st.sampled_from([0, 0, 1, 2]))
@settings(max_examples=60, deadline=None, derandomize=True)
def test_differential_keyword(seed, n_rows, parts, threshold, token, ed):
    rng = random.Random(seed)
    ds = _build(rng, n_rows, parts, threshold, index_kinds=("txt",))
    if ed == 0:
        pred = lambda r: "txt" in r and token in word_tokens(r["txt"])  # noqa: E731
    else:
        pred = lambda r: "txt" in r and any(  # noqa: E731
            edit_distance_check(t, token, ed)
            for t in word_tokens(r["txt"]))
    plan = A.select(A.scan("D"), pred=pred, fields=["txt"],
                    keyword=("txt", token, ed))
    _assert_engines_agree(ds, plan)


def _check_columnar_primary(ds):
    """Every disk-resident primary component keeps ColumnBatch + tombstone
    bitmap as its *primary* data — no retained row list, no stale per-
    column cache (the pre-refactor double representation)."""
    for part in ds.partitions:
        for comp in part.primary.components:
            if comp.valid:
                assert comp.batch is not None
                assert comp.tomb is not None
                assert not hasattr(comp, "col_cache")


def _index_probe_plan(rng, kind):
    """A select whose access path exercises the per-component CSR
    postings of the given index kind (the lifecycle schedules interleave
    these with flush/merge/recover so candidates migrate across every
    storage tier)."""
    if kind == "spatial":
        center = (rng.uniform(0.0, 1.0), rng.uniform(0.0, 1.0))
        radius = rng.uniform(0.05, 0.4)
        return A.select(
            A.scan("D"),
            pred=lambda r: "loc" in r
            and spatial_distance(r["loc"], center) <= radius,
            fields=["loc"], spatial=("loc", center, radius))
    token = rng.choice(VOCAB)
    ed = rng.choice([0, 0, 1, 2])
    if ed == 0:
        pred = lambda r: "txt" in r and token in word_tokens(r["txt"])  # noqa: E731
    else:
        pred = lambda r: "txt" in r and any(  # noqa: E731
            edit_distance_check(t, token, ed)
            for t in word_tokens(r["txt"]))
    return A.select(A.scan("D"), pred=pred, fields=["txt"],
                    keyword=("txt", token, ed))


@given(st.integers(0, 10 ** 9), st.integers(2, 4),
       st.sampled_from([6, 13, 31]))
@settings(max_examples=40, deadline=None, derandomize=True)
def test_differential_lifecycle_schedules(seed, parts, threshold):
    """Interleaved insert / insert_batch / delete / explicit flush /
    explicit merge / crash_and_recover schedules: row and columnar
    engines stay in lockstep at every checkpoint, and components created
    by any flush or merge carry columnar primary data throughout.
    Queries cover every secondary CSR kind (btree / rtree / keyword), so
    postings built at flush/merge, backfilled, and rebuilt from memtable
    tails all get exercised mid-lifecycle."""
    rng = random.Random(seed)
    ds = PartitionedDataset(
        "D", _record_type(), "id", num_partitions=parts,
        flush_threshold=threshold,
        merge_policy=TieredMergePolicy(k=rng.choice([2, 3])))
    ds.create_index("a")
    ds.create_index("txt", kind="keyword")
    ds.create_index("loc", kind="rtree")
    key_space = 120

    def mk_row():
        r = {"id": rng.randrange(key_space), "g": rng.randrange(4)}
        if rng.random() < 0.8:
            r["a"] = rng.randrange(-50, 50)
        if rng.random() < 0.6:
            r["txt"] = " ".join(rng.choice(VOCAB) for _ in range(2))
        if rng.random() < 0.5:
            r["loc"] = (rng.uniform(0.0, 1.0), rng.uniform(0.0, 1.0))
        if rng.random() < 0.4:   # open field of drifting kind
            r["x"] = rng.choice([rng.randrange(100), rng.uniform(0.0, 9.0),
                                 rng.choice(VOCAB)])
        return r

    for _ in range(rng.randrange(4, 9)):
        op = rng.choice(["insert", "insert", "batch", "delete", "flush",
                         "merge", "recover", "query"])
        if op == "insert":
            for _ in range(rng.randrange(1, threshold + 3)):
                ds.insert(mk_row())
        elif op == "batch":
            ds.insert_batch(
                [mk_row() for _ in range(rng.randrange(1, 2 * threshold))])
        elif op == "delete":
            for _ in range(rng.randrange(1, 6)):
                ds.delete(rng.randrange(key_space))
        elif op == "flush":
            for part in ds.partitions:
                part.primary.flush()
        elif op == "merge":
            part = ds.partitions[rng.randrange(parts)]
            valid = [c for c in part.primary.components if c.valid]
            if len(valid) >= 2:
                k = rng.randrange(2, len(valid) + 1)
                start = rng.randrange(0, len(valid) - k + 1)
                part.primary.merge(valid[start:start + k])
        elif op == "recover":
            ds.crash_and_recover()
        else:
            kind = rng.choice(["btree", "agg", "group", "topk",
                               "spatial", "keyword"])
            if kind in ("spatial", "keyword"):
                _assert_engines_agree(ds, _index_probe_plan(rng, kind))
            else:
                _assert_engines_agree(ds, _relational_plan(rng, kind))
        _check_columnar_primary(ds)
    _assert_engines_agree(ds, _relational_plan(rng, "multi"))
    for kind in ("spatial", "keyword"):
        _assert_engines_agree(ds, _index_probe_plan(rng, kind))
    _check_columnar_primary(ds)


def test_merge_gathers_columns_without_forcing_rows():
    """The column-wise merge path materializes no row dicts: merging
    components whose lazy row view was never forced leaves every input
    — and the merged output — with ``_rows`` unset, while contents
    (string dictionaries included) stay exact."""
    ix = LSMIndex(flush_threshold=4, merge_policy=TieredMergePolicy(k=99))
    for i in range(16):
        ix.insert(i, {"id": i, "v": f"s{i % 5}", "w": i * 2})
    for i in (2, 7):
        ix.delete(i)
    ix.flush()                                    # tombstones flush too
    comps = [c for c in ix.components if c.valid]
    assert len(comps) >= 2
    assert all(c.batch is not None and c._rows is None for c in comps)
    out = ix.merge(comps)                         # includes the oldest
    assert out.valid and out.batch is not None
    assert out._rows is None                      # no row materialized
    assert all(c._rows is None for c in comps)    # inputs never forced
    assert not out.tomb.any()                     # tombstones collapsed
    # contents exact (this forces the lazy view — only now, on demand)
    assert dict(ix.items()) == {i: {"id": i, "v": f"s{i % 5}", "w": i * 2}
                                for i in range(16) if i not in (2, 7)}


def test_index_plans_never_silently_fall_back():
    """Every index access path must lower onto the columnar engine on a
    dataset where it is applicable: zero fallback rows, nonzero
    rows_index_vectorized.  Guards the vectorized path against silently
    regressing to the row engine (run by scripts/verify.sh)."""
    rng = random.Random(20260728)
    ds = _build(rng, 120, 3, 16)
    plans = {
        "btree": _btree_select(random.Random(1)),
        "multi": _multi_select(random.Random(2)),
        "spatial": A.select(
            A.scan("D"),
            pred=lambda r: "loc" in r
            and spatial_distance(r["loc"], (0.5, 0.5)) <= 0.4,
            fields=["loc"], spatial=("loc", (0.5, 0.5), 0.4)),
        "keyword": A.select(
            A.scan("D"),
            pred=lambda r: "txt" in r and "jax" in word_tokens(r["txt"]),
            fields=["txt"], keyword=("txt", "jax", 0)),
        "agg_over_index": A.aggregate(
            A.select(A.scan("D"), pred=_range_pred("a", -10, 40),
                     fields=["a"], ranges={"a": (-10, 40)}),
            {"c": ("count", "*"), "s": ("sum", "a")}),
    }
    for name, plan in plans.items():
        if "skip-index" in (plan.attrs.get("hints") or ()):
            plan.attrs["hints"] = ()
        ex = _assert_engines_agree(ds, plan)
        assert ex.stats.rows_fallback == 0, name
        # fallback reasons are recorded per-op: a fully lowered plan has
        # none, and a regression here now names the op + why it fell back
        assert ex.stats.fallback_reasons == {}, (name,
                                                 ex.stats.fallback_reasons)
        assert ex.stats.rows_index_vectorized > 0, name
        # repeated query over the (now warm) postings + padded batches:
        # no kernel core may retrace
        ex2 = _assert_engines_agree(ds, plan)
        assert ex2.stats.kernel_retraces == 0, name
    # the fuzzy ngram chain gets the same guard (on a dataset whose txt
    # index is ngram-kind), counting into rows_fuzzy_vectorized
    from repro.fuzzy import fuzzy_predicate
    ds2 = _build(random.Random(20260729), 120, 3, 16,
                 index_kinds=("a", "txt"), txt_kind="ngram")
    for spec in [("txt", "ed", "tonight", 2),
                 ("txt", "jaccard", "coffee", 0.4)]:
        plan = A.select(A.scan("D"), pred=fuzzy_predicate(spec),
                        fields=["txt"], fuzzy=spec)
        ex = _assert_engines_agree(ds2, plan)
        assert ex.stats.rows_fallback == 0, spec
        assert ex.stats.fallback_reasons == {}, (spec,
                                                 ex.stats.fallback_reasons)
        assert ex.stats.rows_fuzzy_vectorized > 0, spec


# ---------------------------------------------------------------------------
# mesh axis: the SPMD partition runtime is bit-identical to the loop
# ---------------------------------------------------------------------------

import jax as _jax  # noqa: E402

_N_DEV = len(_jax.devices())


def _assert_mesh_agrees(ds, plan, devs):
    """Columnar loop mode vs mesh mode: identical rows AND identical
    fallback accounting (the mesh path may decline work — per-partition
    None entries — but never change *why* an op fell back)."""
    rows_c, ex_c = run_query(plan, {"D": ds}, vectorize=True)
    rows_m, ex_m = run_query(plan, {"D": ds}, vectorize=True, mesh=devs)
    assert _canon(rows_c) == _canon(rows_m), \
        f"loop={len(rows_c)} mesh={len(rows_m)}"
    assert ex_c.stats.fallback_reasons == ex_m.stats.fallback_reasons
    return ex_m


@pytest.mark.parametrize("devs", [
    1,
    pytest.param(2, marks=pytest.mark.skipif(
        _N_DEV < 2, reason="needs >=2 devices (forced-multi-device CI "
        "leg sets XLA_FLAGS=--xla_force_host_platform_device_count=4)")),
    pytest.param(4, marks=pytest.mark.skipif(
        _N_DEV < 4, reason="needs >=4 devices"))])
def test_differential_mesh_lifecycle_schedules(devs):
    """Random dataset lifecycles (flush/merge/recover interleaved by
    _build) queried under an active partition mesh stay bit-identical
    to the 1-device Python-loop fallback — rows and fallback reasons —
    and warm mesh queries retrace nothing.  Runs at mesh size 1
    everywhere (full shard_map machinery on the default single
    CpuDevice) and at 2/4 under the forced-multi-device CI leg."""
    rng = random.Random(20260807 * devs + 11)
    for _case in range(12):
        ds = _build(rng, rng.randrange(0, 90), rng.choice([2, 3, 4]),
                    rng.choice([4, 9, 17, 33]), index_kinds=("a", "b"))
        kind = rng.choice(["btree", "multi", "agg", "group", "topk",
                           "project"])
        _assert_mesh_agrees(ds, _relational_plan(rng, kind), devs)
    # explicit lifecycle interleaving: query checkpoints under the mesh
    ds = PartitionedDataset(
        "D", _record_type(), "id", num_partitions=4, flush_threshold=9,
        merge_policy=TieredMergePolicy(k=2))
    ds.create_index("a")
    for step in range(6):
        for i in range(18):
            r = {"id": rng.randrange(200), "g": rng.randrange(4)}
            if rng.random() < 0.9:
                r["a"] = rng.randrange(-50, 50)
            ds.insert(r)
        if step == 2:
            for part in ds.partitions:
                part.primary.flush()
        if step == 3:
            ds.delete(rng.randrange(200))
        if step == 4:
            ds.crash_and_recover()
        _assert_mesh_agrees(ds, _relational_plan(rng, "agg"), devs)
    # warm mesh repeat over the settled dataset: zero retraces
    plan = _relational_plan(random.Random(3), "agg")
    run_query(plan, {"D": ds}, vectorize=True, mesh=devs)
    _, ex = run_query(plan, {"D": ds}, vectorize=True, mesh=devs)
    assert ex.stats.kernel_retraces == 0
