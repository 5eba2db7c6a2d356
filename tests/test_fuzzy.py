"""Fuzzy query subsystem: similarity kernels vs scalar oracles, ngram
postings structure across the LSM lifecycle, T-occurrence candidate
soundness, plan lowering (row vs columnar, counters, zero retraces), and
the batched FuzzyJoin verify."""

import random

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container has no hypothesis: seeded shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import adm
from repro.core import algebra as A
from repro.core.functions import (edit_distance, edit_distance_check,
                                  gram_tokens, similarity_jaccard)
from repro.core.lsm import LSMIndex, TieredMergePolicy
from repro.data.dedup import FuzzyJoin, _token_hash, _token_hashes
from repro import obs
from repro.columnar.batch import ColumnBatch
from repro.fuzzy import (GRAM_K, GramPostings, fuzzy_predicate, query_grams,
                         spec_gram_length, value_gram_hashes, verify_mask,
                         verify_values)
from repro.fuzzy import ngram
from repro.kernels import fuzzy_ops as F
from repro.storage.dataset import PartitionedDataset
from repro.storage.query import run_query

WORDS = ["tonight", "tonite", "tonigh", "tonightt", "coffee", "covfefe",
         "jax", "pallas", "mesh", "verona", "aaaaaaa", "aaaaaa", ""]


def _rng_word(rng, n=10):
    return "".join(rng.choice("abcde#") for _ in range(rng.randrange(n)))


# ---------------------------------------------------------------------------
# kernels vs oracles
# ---------------------------------------------------------------------------

def test_fnv1a_matches_scalar_loop():
    toks = ["", "a", "hello", "café", "x" * 50, "tonight"]

    def scalar(t):          # the classic per-byte FNV-1a-64 oracle
        h = 14695981039346656037
        for byte in t.encode():
            h = ((h ^ byte) * 1099511628211) % (1 << 64)
        return h

    assert [int(x) for x in F.fnv1a_hash(toks)] == \
        [scalar(t) for t in toks]
    # the Mersenne-reduced path is bit-identical to dedup._token_hash
    assert [int(x) for x in _token_hashes(toks)] == \
        [_token_hash(t) for t in toks]


@given(st.integers(0, 10 ** 9), st.integers(1, 9))
@settings(max_examples=30, deadline=None, derandomize=True)
def test_t_occurrence_matches_bincount(seed, threshold):
    """``ngram_t_occurrence`` over random per-tier postings and query
    segments (including empty tiers and segments) vs a bincount of the
    gathered hits; sizes span the host floor and the device path."""
    rng = np.random.default_rng(seed)
    tiers, hits, off = [], [], 0
    for _ in range(int(rng.integers(1, 5))):
        rows = int(rng.integers(1, 3000))
        lens = rng.integers(0, 40, int(rng.integers(0, 60)))
        pos = rng.integers(0, rows, int(lens.sum())).astype(np.int64)
        bounds = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
        pick = np.flatnonzero(rng.random(lens.shape[0]) < 0.5)
        st_, en = bounds[pick], bounds[pick + 1]
        padded = np.zeros(max(1, 1 << int(pos.shape[0] - 1).bit_length()),
                          dtype=np.int64)
        padded[:pos.shape[0]] = pos
        tiers.append((off, pos, padded, st_, en))
        hits += [off + pos[a:b] for a, b in zip(st_, en)]
        off += rows
    allp = np.concatenate(hits) if hits else np.zeros(0, np.int64)
    oracle = np.bincount(allp, minlength=off) >= threshold
    assert (F.ngram_t_occurrence(tiers, off, threshold) == oracle).all()


@given(st.integers(0, 10 ** 9), st.integers(0, 4))
@settings(max_examples=25, deadline=None, derandomize=True)
def test_banded_dp_matches_edit_distance_oracle(seed, d):
    rng = random.Random(seed)
    cands = [_rng_word(rng, 12) for _ in range(50)] + WORDS
    q = rng.choice(cands)
    oracle = np.asarray([min(edit_distance(c, q), d + 1) for c in cands])
    assert (F._ed_jnp(cands, q, d) == oracle).all()
    assert (F._ed_pallas(cands, q, d, interpret=True) == oracle).all()
    assert (F.edit_distances(cands, q, d) == oracle).all()
    # the whole-dictionary variant (one resident char matrix per owner)
    owner = np.asarray(cands, dtype=object)
    for pallas in (False, True):
        assert (F.dictionary_edit_distances(owner, cands, q, d,
                                            force_pallas=pallas,
                                            interpret=True) == oracle).all()


@given(st.integers(0, 10 ** 9))
@settings(max_examples=25, deadline=None, derandomize=True)
def test_batched_jaccard_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    P = int(rng.integers(1, 60))
    sets_a = [np.unique(rng.integers(0, 40, rng.integers(0, 25)))
              .astype(np.int64) for _ in range(P)]
    sets_b = [np.unique(rng.integers(0, 40, rng.integers(0, 25)))
              .astype(np.int64) for _ in range(P)]
    inter_o = np.asarray([len(set(a.tolist()) & set(b.tolist()))
                          for a, b in zip(sets_a, sets_b)])
    sims_o = np.asarray([similarity_jaccard(set(a.tolist()),
                                            set(b.tolist()))
                         for a, b in zip(sets_a, sets_b)])
    assert (F.set_intersect_counts(sets_a, sets_b) == inter_o).all()
    am, al, _ = F._pad_sets(sets_a, np.int64(0))
    bm, _, _ = F._pad_sets(sets_b, F._SENTINEL)
    assert (F._inter_jnp(am, al, bm)[:P] == inter_o).all()
    assert (F.jaccard_sims(sets_a, sets_b) == sims_o).all()
    # bitset/popcount variant over the same pairs
    sizes = np.fromiter((len(s) for s in sets_a + sets_b), np.int64,
                        count=2 * P)
    codes = np.concatenate(sets_a + sets_b) if sizes.sum() \
        else np.zeros(0, dtype=np.int64)
    seg = np.repeat(np.arange(2 * P, dtype=np.int64), sizes)
    bits = F.encode_bitsets(codes.astype(np.int64), seg, 2 * P, 40)
    ai = np.arange(P, dtype=np.int64)
    bi = np.arange(P, 2 * P, dtype=np.int64)
    assert (F.bitset_intersect_counts(bits, ai, bi) == inter_o).all()


# ---------------------------------------------------------------------------
# T-occurrence bounds: candidates are always a superset of true matches
# ---------------------------------------------------------------------------

@given(st.integers(0, 10 ** 9), st.integers(0, 3),
       st.sampled_from([0.2, 0.5, 0.8]))
@settings(max_examples=30, deadline=None, derandomize=True)
def test_t_occurrence_bound_soundness(seed, d, t):
    """Every value passing the scalar predicate must clear the
    T-occurrence count — including the repeated-gram worst cases the
    distinct-gram bound is stated for (e.g. 'aaaaaaa' vs 'aaaaaa')."""
    rng = random.Random(seed)
    k = 3
    vals = [_rng_word(rng, 12) for _ in range(60)] + WORDS
    for target in (rng.choice(vals), "aaaaaaa", "tonight"):
        for kind, param in (("ed", d), ("jaccard", t)):
            qh, T = query_grams(("w", kind, target, param), k)
            for v in vals:
                hits = len(np.intersect1d(value_gram_hashes(v, k), qh,
                                          assume_unique=True))
                if kind == "ed":
                    matches = edit_distance_check(v, target, param)
                else:
                    matches = similarity_jaccard(
                        set(gram_tokens(v, k)),
                        set(gram_tokens(target, k))) >= param
                if matches:
                    assert hits >= T, (kind, v, target, param, hits, T)


@given(st.integers(0, 10 ** 9), st.integers(0, 3),
       st.sampled_from([0.2, 0.5, 0.8]))
@settings(max_examples=20, deadline=None, derandomize=True)
def test_verify_values_matches_scalar_predicates(seed, d, t):
    rng = random.Random(seed)
    vals = [_rng_word(rng, 12) for _ in range(40)] + WORDS
    target = rng.choice(vals)
    got_ed = verify_values(vals, ("w", "ed", target, d), 3)
    assert got_ed.tolist() == [edit_distance_check(v, target, d)
                               for v in vals]
    got_j = verify_values(vals, ("w", "jaccard", target, t), 3)
    assert got_j.tolist() == [
        similarity_jaccard(set(gram_tokens(v, 3)),
                           set(gram_tokens(target, 3))) >= t
        for v in vals]


# ---------------------------------------------------------------------------
# GramPostings structure + LSM lifecycle
# ---------------------------------------------------------------------------

def test_gram_postings_csr_structure():
    vals = ["tonight", None, "tonite", "tonight", 7, "coffee"]
    p = GramPostings.from_values(vals, 3)
    assert p.n_rows == 6
    assert p.has_value.tolist() == [True, False, True, True, False, True]
    # sorted distinct gram dictionary, monotone offsets
    assert (np.diff(p.grams.astype(np.uint64).view(np.uint64)) > 0).all()
    assert p.offsets[0] == 0 and p.offsets[-1] == len(p.positions)
    assert (np.diff(p.offsets) > 0).all()
    # a query for 'tonight' grams hits rows 0 and 3 for every gram
    qh = value_gram_hashes("tonight", 3)
    hits = np.bincount(p.hit_positions(qh), minlength=6)
    assert hits[0] == len(qh) and hits[3] == len(qh)
    assert hits[1] == 0 and hits[4] == 0


def test_gram_postings_from_column_matches_from_values():
    from repro.columnar.batch import ColumnBatch
    rows = [{"w": w} if w is not None else {}
            for w in ["tonight", None, "tonite", "tonight", "coffee", None]]
    batch = ColumnBatch.from_rows(rows)
    pc = GramPostings.from_batch(batch, "w", 3, len(rows))
    pv = GramPostings.from_values(
        [r.get("w") for r in rows], 3)
    assert (pc.grams == pv.grams).all()
    assert (pc.offsets == pv.offsets).all()
    assert pc.has_value.tolist() == pv.has_value.tolist()
    qh = value_gram_hashes("tonight", 3)
    assert sorted(pc.hit_positions(qh).tolist()) == \
        sorted(pv.hit_positions(qh).tolist())


def test_components_carry_postings_through_flush_merge():
    """Postings are built at flush and merge alongside the batch — and
    never by forcing the lazy row view."""
    ix = LSMIndex(flush_threshold=4, merge_policy=TieredMergePolicy(k=99),
                  ngram_fields={"w": 3})
    for i in range(16):
        ix.insert(i, {"id": i, "w": f"word{i % 5}"})
    ix.delete(3)
    ix.flush()
    comps = [c for c in ix.components if c.valid]
    assert len(comps) >= 2
    for c in comps:
        assert "w" in c.gram_postings          # built at flush
        assert c._rows is None                 # without forcing rows
        assert c.gram_postings["w"].n_rows == c.size
    out = ix.merge(comps)
    assert "w" in out.gram_postings            # rebuilt at merge
    assert out._rows is None
    assert out.gram_postings["w"].n_rows == out.size
    # tombstoned row (pk 3) has no indexable value in the merged postings
    pos3 = int(np.searchsorted(out.keys, 3))
    assert not out.gram_postings["w"].has_value[pos3] or out.tomb[pos3] \
        or out.keys[pos3] != 3


def _fuzzy_ds(rng, n=160, parts=3, threshold=8):
    rt = adm.RecordType("T", (adm.Field("id", adm.INT64),
                              adm.Field("w", adm.STRING, optional=True)),
                        open=True)
    ds = PartitionedDataset("D", rt, "id", num_partitions=parts,
                            flush_threshold=threshold,
                            merge_policy=TieredMergePolicy(k=2))
    ds.create_index("w", kind="ngram")
    for i in range(n):
        r = {"id": i}
        if rng.random() < 0.9:
            r["w"] = rng.choice(WORDS[:-1])
        if rng.random() < 0.2:       # open-field drift onto the same name
            r["x"] = rng.choice([1, "one", 2.0])
        ds.insert(r)
    for i in range(0, n, 9):
        ds.delete(i)
    return ds


def _canon(rows):
    return sorted(repr(sorted(r.items(), key=lambda kv: kv[0]))
                  for r in rows)


@pytest.mark.parametrize("spec", [
    ("w", "ed", "tonight", 2),
    ("w", "ed", "tonight", 0),
    ("w", "ed", "covfefe", 3),
    ("w", "jaccard", "tonight", 0.5),
    ("w", "jaccard", "coffee", 0.2),
])
def test_fuzzy_select_row_vs_columnar(spec):
    rng = random.Random(sum(map(ord, repr(spec))))
    ds = _fuzzy_ds(rng)
    plan = A.select(A.scan("D"), pred=fuzzy_predicate(spec),
                    fields=["w"], fuzzy=spec)
    rows_r, _ = run_query(plan, {"D": ds})
    rows_c, ex = run_query(plan, {"D": ds}, vectorize=True)
    assert _canon(rows_r) == _canon(rows_c)
    assert ex.stats.rows_fallback == 0
    assert ex.stats.rows_fuzzy_vectorized > 0
    # oracle: full scan with the scalar predicate
    oracle = [r for r in ds.scan() if fuzzy_predicate(spec)(r)]
    assert _canon(rows_r) == _canon(oracle)


def test_fuzzy_select_across_lifecycle_and_recovery():
    rng = random.Random(20260729)
    ds = _fuzzy_ds(rng, n=200, parts=4, threshold=16)
    spec = ("w", "ed", "tonight", 2)
    plan = A.select(A.scan("D"), pred=fuzzy_predicate(spec),
                    fields=["w"], fuzzy=spec)

    def check():
        rows_r, _ = run_query(plan, {"D": ds})
        rows_c, ex = run_query(plan, {"D": ds}, vectorize=True)
        assert _canon(rows_r) == _canon(rows_c)
        assert ex.stats.rows_fallback == 0
        assert ex.stats.rows_fuzzy_vectorized > 0
        return len(rows_r)

    assert check() > 0
    ds.insert({"id": 9999, "w": "tonigt"})    # memtable-resident match
    assert check() > 0
    for p in ds.partitions:
        p.primary.flush()
    check()
    ds.crash_and_recover()
    check()
    # repeated query after warmup: the jitted fuzzy cores never retrace
    run_query(plan, {"D": ds}, vectorize=True)
    _, ex = run_query(plan, {"D": ds}, vectorize=True)
    assert ex.stats.kernel_retraces == 0


def test_late_ngram_index_backfills_existing_components():
    rng = random.Random(7)
    rt = adm.RecordType("T", (adm.Field("id", adm.INT64),
                              adm.Field("w", adm.STRING, optional=True)),
                        open=True)
    ds = PartitionedDataset("D", rt, "id", num_partitions=2,
                            flush_threshold=8)
    for i in range(60):
        ds.insert({"id": i, "w": rng.choice(WORDS[:-1])})
    for p in ds.partitions:
        p.primary.flush()
    ds.create_index("w", kind="ngram")        # late: backfill on disk comps
    for p in ds.partitions:
        for c in p.primary.components:
            if c.valid:
                assert "w" in c.gram_postings
    spec = ("w", "jaccard", "tonight", 0.5)
    plan = A.select(A.scan("D"), pred=fuzzy_predicate(spec),
                    fields=["w"], fuzzy=spec)
    rows_r, _ = run_query(plan, {"D": ds})
    rows_c, ex = run_query(plan, {"D": ds}, vectorize=True)
    assert _canon(rows_r) == _canon(rows_c)
    assert ex.stats.rows_fuzzy_vectorized > 0


def test_fuzzy_select_pred_with_extra_conjunct_not_dropped():
    """pred may carry conjuncts beyond the fuzzy spec; without
    ``ranges_exact`` the columnar chain must re-check it on survivors
    (regression: the extra conjunct used to be silently dropped)."""
    rng = random.Random(3)
    ds = _fuzzy_ds(rng, n=150)
    spec = ("w", "ed", "tonight", 2)
    fz = fuzzy_predicate(spec)
    plan = A.select(A.scan("D"),
                    pred=lambda r: fz(r) and r["id"] % 2 == 0,
                    fields=["w", "id"], fuzzy=spec)
    rows_r, _ = run_query(plan, {"D": ds})
    rows_c, ex = run_query(plan, {"D": ds}, vectorize=True)
    assert _canon(rows_r) == _canon(rows_c)
    assert all(r["id"] % 2 == 0 for r in rows_c)
    assert ex.stats.rows_fallback == 0
    assert ex.stats.rows_fuzzy_vectorized > 0


def _count_oracle_calls(monkeypatch):
    """Count the scalar checks the fuzzy oracle makes (one per row it
    decides)."""
    calls = []
    for name in ("edit_distance_check", "similarity_jaccard_check"):
        real = getattr(ngram, name)
        monkeypatch.setattr(
            ngram, name,
            lambda *a, _real=real: calls.append(a) or _real(*a))
    return calls


def _fuzzy_counts():
    snap = obs.snapshot()
    return (snap.get("fuzzy.selects", 0),
            snap.get("fuzzy.selects_rechecked", 0))


@pytest.mark.parametrize("pred_spec,fuzzy_spec", [
    (("w", "ed", "tonight", 2), ("w", "ed", "tonight", 2)),
    (("w", "ed", "tonight", 2.0), ("w", "ed", "tonight", 2.0)),
    (("w", "ed", "tonight", 2), ("w", "ed", "tonight", 2.0)),
    (("w", "jaccard", "tonight", 0.5), ("w", "jaccard", "tonight", 0.5)),
    (("w", "jaccard", "tonight", 0.5), ("w", "jaccard", "tonight", 0.5, 3)),
    (("w", "jaccard", "tonite", 1), ("w", "jaccard", "tonite", 1.0)),
])
def test_fuzzy_select_own_oracle_skips_row_recheck(pred_spec, fuzzy_spec,
                                                   monkeypatch):
    """``pred=fuzzy_predicate(spec), fuzzy=spec`` (the spec compared in
    normal form): the batched verify's answer stands, no row goes
    through pred, and the answer is the oracle's."""
    rng = random.Random(sum(map(ord, repr(fuzzy_spec))))
    ds = _fuzzy_ds(rng)
    plan = A.select(A.scan("D"), pred=fuzzy_predicate(pred_spec),
                    fields=["w"], fuzzy=fuzzy_spec)
    calls = _count_oracle_calls(monkeypatch)
    before = _fuzzy_counts()
    rows_c, ex = run_query(plan, {"D": ds}, vectorize=True)
    after = _fuzzy_counts()
    assert calls == []
    assert (after[0] - before[0], after[1] - before[1]) == (1, 0)
    assert ex.stats.rows_fallback == 0
    assert ex.stats.rows_fuzzy_vectorized > 0
    rows_r, _ = run_query(plan, {"D": ds})
    oracle = [r for r in ds.scan() if fuzzy_predicate(pred_spec)(r)]
    assert rows_c
    assert _canon(rows_c) == _canon(rows_r) == _canon(oracle)


@pytest.mark.parametrize("case", ["extra_conjunct", "other_spec", "ranges"])
def test_fuzzy_select_other_pred_keeps_row_recheck(case):
    """Any pred but the select's own oracle keeps the row re-check: the
    oracle wrapped with an extra conjunct, the oracle of another spec
    than ``fuzzy=``, and the oracle beside declared ranges."""
    rng = random.Random(5)
    ds = _fuzzy_ds(rng, n=150)
    spec = ("w", "ed", "tonight", 2)
    fz = fuzzy_predicate(spec)
    kw = {}
    if case == "extra_conjunct":
        pred = lambda r: fz(r) and r["id"] % 2 == 0
    elif case == "other_spec":
        pred = fuzzy_predicate(("w", "ed", "tonight", 1))
    else:
        pred = fz                   # every id lies in the declared range
        kw["ranges"] = {"id": (0, 10 ** 6)}
    plan = A.select(A.scan("D"), pred=pred, fields=["w", "id"],
                    fuzzy=spec, **kw)
    before = _fuzzy_counts()
    rows_c, ex = run_query(plan, {"D": ds}, vectorize=True)
    after = _fuzzy_counts()
    assert (after[0] - before[0], after[1] - before[1]) == (1, 1)
    assert ex.stats.rows_fallback == 0
    rows_r, _ = run_query(plan, {"D": ds})
    oracle = [r for r in ds.scan() if pred(r)]
    assert rows_c
    assert _canon(rows_c) == _canon(rows_r) == _canon(oracle)


def test_fuzzy_predicate_normal_form():
    assert fuzzy_predicate(("w", "ed", "x", 2)).spec \
        == fuzzy_predicate(("w", "ed", "x", 2.0)).spec
    j = fuzzy_predicate(("w", "jaccard", "x", 0.5))
    assert j.decides(("w", "jaccard", "x", 0.5, GRAM_K))
    assert not j.decides(("w", "jaccard", "x", 0.5, 2))
    assert not j.decides(("w", "jaccard", "x", 0.4))
    assert not j.decides(("v", "jaccard", "x", 0.5))
    assert not fuzzy_predicate(("w", "ed", "x", 2)).decides(
        ("w", "ed", "y", 2))
    with pytest.raises(ValueError):
        fuzzy_predicate(("w", "soundex", "x", 1))


# Edge values for the batched verify, on which the skipped re-check now
# rests: the empty string, non-ASCII, lengths d and d + 1 away from the
# target, None and non-strings in an ``obj`` column.
_EDGE_VALUES = ["", "t", "to", "tonight", "tönight", "tonigh", "tonig",
                "toni", "tonightxy", "tonightxyz", "café", "東京タワー",
                "TONIGHT", "tøñîght", "nightto", "ton ight"]


@pytest.mark.parametrize("target", ["tonight", "tönight", ""])
@pytest.mark.parametrize("spec_tail", [("ed", 0), ("ed", 1), ("ed", 2),
                                       ("ed", 2.5), ("ed", -0.5),
                                       ("jaccard", 0.5)])
@pytest.mark.parametrize("many", [False, True],
                         ids=["host_dp", "dictionary_dp"])
@pytest.mark.parametrize("kind", ["str", "obj"])
def test_verify_mask_matches_oracle_on_edge_values(target, spec_tail, many,
                                                   kind):
    rng = random.Random(7)
    vals = list(_EDGE_VALUES)
    if many:                        # above the host DP's batch floor
        vals += [_rng_word(rng, 12) + str(i)
                 for i in range(F.HOST_ED_MAX + 8)]
    rows = [{"id": i, "w": v} for i, v in enumerate(vals)]
    if kind == "obj":
        rows += [{"id": -1, "w": None}, {"id": -2, "w": 7},
                 {"id": -3, "w": 2.5}, {"id": -4}, {"id": -5, "w": ["t"]}]
    batch = ColumnBatch.from_rows(rows)
    assert batch.columns["w"].kind == kind
    spec = ("w",) + spec_tail[:1] + (target,) + spec_tail[1:]
    oracle = fuzzy_predicate(spec)
    want = np.asarray([oracle(r) for r in rows])
    mask = np.ones(len(rows), dtype=bool)
    mask[1::5] = False              # some rows are not candidates
    got = verify_mask(batch, mask, spec, spec_gram_length(spec))
    assert got.tolist() == (want & mask).tolist()


def test_jaccard_spec_gram_length_differs_from_index():
    """A jaccard spec pinned to its own gram length (5th element) stays
    correct on an index built with a different k: the T-occurrence bound
    would be unsound, so candidate pruning turns off (all valued rows)
    and the batched verify — run at the *spec's* k — decides (regression:
    the verify used to run at the index's k, diverging from the
    oracle)."""
    rng = random.Random(9)
    rt = adm.RecordType("T", (adm.Field("id", adm.INT64),
                              adm.Field("w", adm.STRING, optional=True)),
                        open=True)
    ds = PartitionedDataset("D", rt, "id", num_partitions=2,
                            flush_threshold=8)
    ds.create_index("w", kind="ngram", gram_length=2)
    for i in range(80):
        ds.insert({"id": i, "w": rng.choice(WORDS[:-1])})
    # default-k (3) spec and an explicitly pinned k=2 spec, both on the
    # ngram(2) index
    for spec in [("w", "jaccard", "tonight", 0.5),
                 ("w", "jaccard", "tonight", 0.5, 2),
                 ("w", "ed", "tonight", 2)]:
        plan = A.select(A.scan("D"), pred=fuzzy_predicate(spec),
                        fields=["w"], fuzzy=spec)
        rows_r, _ = run_query(plan, {"D": ds})
        rows_c, ex = run_query(plan, {"D": ds}, vectorize=True)
        assert _canon(rows_r) == _canon(rows_c), spec
        assert ex.stats.rows_fallback == 0, spec
        oracle = [r for r in ds.scan() if fuzzy_predicate(spec)(r)]
        assert _canon(rows_r) == _canon(oracle), spec


def test_ngram_index_on_mixed_kind_open_field():
    """An ngram index over an *open* field whose values drift between
    strings and ints (an ``obj`` column after shredding): non-strings are
    never candidates, engines agree, nothing falls back."""
    rng = random.Random(1)
    rt = adm.RecordType("T", (adm.Field("id", adm.INT64),), open=True)
    ds = PartitionedDataset("D", rt, "id", num_partitions=2,
                            flush_threshold=6)
    ds.create_index("x", kind="ngram")
    for i in range(60):
        r = {"id": i}
        c = rng.random()
        if c < 0.4:
            r["x"] = rng.choice(["tonight", "tonite", "coffee"])
        elif c < 0.7:
            r["x"] = rng.randrange(100)
        ds.insert(r)
    for spec in [("x", "ed", "tonight", 2),
                 ("x", "jaccard", "tonight", 0.4)]:
        plan = A.select(A.scan("D"), pred=fuzzy_predicate(spec),
                        fields=["x"], fuzzy=spec)
        rows_r, _ = run_query(plan, {"D": ds})
        rows_c, ex = run_query(plan, {"D": ds}, vectorize=True)
        assert _canon(rows_r) == _canon(rows_c), spec
        assert ex.stats.rows_fallback == 0, spec
        assert all(isinstance(r["x"], str) for r in rows_c)


def test_keyword_fuzzy_scan_is_batched_and_exact():
    """The keyword fuzzy path (per-token edit distance) now batches the
    token dictionary through the DP kernel — results unchanged."""
    from repro.core.functions import word_tokens
    rng = random.Random(11)
    rt = adm.RecordType("T", (adm.Field("id", adm.INT64),
                              adm.Field("txt", adm.STRING)), open=True)
    ds = PartitionedDataset("D", rt, "id", num_partitions=2,
                            flush_threshold=16)
    ds.create_index("txt", kind="keyword")
    for i in range(80):
        ds.insert({"id": i, "txt": " ".join(
            rng.choice(WORDS[:-1]) for _ in range(3))})
    got = []
    for i in range(ds.num_partitions):
        got += ds.keyword_search_partition(i, "txt", "tonight", 2)
    oracle = [r["id"] for r in ds.scan()
              if any(edit_distance_check(t, "tonight", 2)
                     for t in word_tokens(r["txt"]))]
    assert sorted(set(got)) == sorted(set(oracle))


# ---------------------------------------------------------------------------
# FuzzyJoin batched verify
# ---------------------------------------------------------------------------

def test_fuzzy_join_batched_verify_matches_per_pair():
    rng = random.Random(5)
    vocab = [f"tok{i}" for i in range(40)]
    recs = [(i, set(rng.sample(vocab, rng.randrange(0, 15))))
            for i in range(150)]
    pairs_b, stats_b = FuzzyJoin(threshold=0.4).run(recs)
    pairs_p, stats_p = FuzzyJoin(threshold=0.4, batch_verify=False).run(recs)
    assert sorted(pairs_b) == sorted(pairs_p)
    assert stats_b["candidates"] == stats_p["candidates"]
    assert stats_b["pairs"] == stats_p["pairs"]
    # reported similarities are the exact float64 jaccard values
    from repro.data.dedup import jaccard
    toks = dict(recs)
    for a, b, j in pairs_b:
        assert j == jaccard(toks[a], toks[b])


def test_fuzzy_join_handles_non_integer_record_ids():
    """Ids that don't survive int64 conversion (non-integral floats,
    huge ints, strings) must take the generic dictionary path, not be
    silently truncated or crash (regression: 2.5 used to truncate to 2
    and 2**63 raised OverflowError)."""
    for ids in [(1.5, 2.5, 3.25), (2 ** 63, 2 ** 63 + 7, 5),
                ("a", "b", "c")]:
        recs = [(ids[0], {"x", "y", "z"}), (ids[1], {"x", "y", "q"}),
                (ids[2], {"p", "q", "r"})]
        toks = dict(recs)
        cands = [(ids[0], ids[1]), (ids[1], ids[2]), (ids[0], ids[2])]
        fj_b = FuzzyJoin(threshold=0.4)
        fj_p = FuzzyJoin(threshold=0.4, batch_verify=False)
        assert sorted(fj_b.verify(cands, toks), key=str) == \
            sorted(fj_p.verify(cands, toks), key=str), ids
