"""ngram(k) postings: the columnar secondary-index structure behind the
fuzzy query paths (the ``"ngram"`` index kind ``core/rewriter`` reserved).

Ngram postings are not an LSMIndex of (key, pk) pairs: each *primary*
component carries a ``GramPostings`` per indexed field, built at
flush/merge alongside the component's ColumnBatch (and from the batch's
string dictionary, not by re-tokenizing rows).  This per-component
derived-columnar-data calculus now covers every secondary kind — the
btree/rtree/keyword structures are the same pattern generalized
(``columnar/postings.FieldPostings``, which also hosts the shared CSR
builders this module uses).  The structure is a columnar CSR:

  grams      sorted distinct uint64 FNV-1a gram hashes
  offsets    int64 [G+1] segment bounds into ``positions``
  positions  int64 component-local row positions, one entry per
             (distinct gram, row) pair
  has_value  bool bitmap: row holds an indexable string at all (the
             T <= 0 fallback candidate set)

Candidate generation is T-occurrence: a query's gram-hit posting
segments (``hit_segments``) are gathered from each tier's
device-resident positions into one count
(``kernels/fuzzy_ops.ngram_t_occurrence``), which keeps positions with
>= T hits.  The thresholds are the classic lower bounds, adjusted for
hashing so collisions can only add false positives (verification removes
them), never false negatives:

  edit distance d    T = |H(set G(q))| - k*d      (an edit destroys at
                     most k gram occurrences, hence at most k distinct
                     gram types)
  jaccard >= t       T = ceil(t * |set G(q)|) - (|set G(q)| - |H(...)|)
                     (J >= t implies |A∩B| >= t*|A∪B| >= t*|A|; the
                     subtrahend discounts in-query hash collisions)
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from ..columnar.batch import pow2_len
from ..columnar.postings import csr_from_pairs, segment_gather
from ..core.functions import (edit_distance_check, gram_tokens,
                              similarity_jaccard_check)
from ..kernels.fuzzy_ops import fnv1a_hash

__all__ = ["GRAM_K", "GramPostings", "FuzzySpec", "spec_gram_length",
           "value_gram_hashes", "query_grams", "FuzzyPredicate",
           "fuzzy_predicate"]

GRAM_K = 3                      # default gram length (AsterixDB's ngram(3))

# (field, kind, target, param[, k]): kind "ed" ->
# edit_distance_check(value, target, param); kind "jaccard" ->
# similarity_jaccard_check over gram_tokens(value, k) vs
# gram_tokens(target, k) at threshold param.  The optional 5th element
# pins the gram length the *predicate* is defined over (default GRAM_K);
# the index's own gram length only shapes the candidate postings.
FuzzySpec = Tuple[str, str, str, Any]


def spec_gram_length(spec: FuzzySpec) -> int:
    """The gram length the spec's predicate semantics are defined over."""
    return int(spec[4]) if len(spec) > 4 else GRAM_K

_EMPTY_U64 = np.zeros(0, dtype=np.uint64)
_EMPTY_I64 = np.zeros(0, dtype=np.int64)


def value_gram_hashes(s: str, k: int) -> np.ndarray:
    """Sorted distinct gram hashes of one string (set semantics: the
    T-occurrence bounds above are stated over distinct grams)."""
    return np.unique(fnv1a_hash(gram_tokens(s, k)))


def values_gram_hashes(values: Sequence[str], k: int
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """``value_gram_hashes`` of many strings at once, as CSR: value v's
    sorted distinct hashes are ``flat[offs[v]:offs[v + 1]]``.  One hash
    pass over every gram and one lexsort dedupe replace the per-value
    calls (dictionary columns of unique strings hash in bulk)."""
    toks = [gram_tokens(v, k) for v in values]
    counts = np.fromiter(map(len, toks), np.int64, count=len(toks))
    offs = np.zeros(len(toks) + 1, dtype=np.int64)
    if not counts.sum():
        return _EMPTY_U64, offs
    h = fnv1a_hash([t for ts in toks for t in ts])
    vid = np.repeat(np.arange(len(toks), dtype=np.int64), counts)
    order = np.lexsort((h, vid))
    h, vid = h[order], vid[order]
    keep = np.ones(h.shape[0], dtype=bool)
    keep[1:] = (h[1:] != h[:-1]) | (vid[1:] != vid[:-1])
    np.cumsum(np.bincount(vid[keep], minlength=len(toks)), out=offs[1:])
    return h[keep], offs


# CSR segment expansion and assembly are shared with the generalized
# secondary postings (columnar/postings.py): one copy of the pattern for
# ngram, btree, rtree and keyword structures.
_segment_gather = segment_gather


@dataclass
class GramPostings:
    """Per-component columnar CSR gram postings (immutable, like the
    component batch it sits beside)."""

    k: int
    grams: np.ndarray       # sorted distinct uint64 hashes
    offsets: np.ndarray     # int64 [G+1]
    positions: np.ndarray   # int64 row positions, grouped by gram
    has_value: np.ndarray   # bool [n_rows]
    n_rows: int
    # pow2-padded positions view, built once per immutable postings
    # (Column.padded idiom): stable identity == stable device-pool key
    _padded: Any = field(default=None, repr=False, compare=False)

    def padded_positions(self) -> np.ndarray:
        """Pow2-padded positions array, built once (zero fill; padding
        lanes must be masked by the caller's CSR offset bounds).  Stable
        identity makes it a device-pool key for the component lifetime."""
        if self._padded is None:
            n = int(self.positions.shape[0])
            np2 = pow2_len(n)
            if np2 == n and n > 0:
                self._padded = self.positions
            else:
                pad = np.zeros(max(np2, 1), dtype=np.int64)
                pad[:n] = self.positions
                self._padded = pad
        return self._padded

    @classmethod
    def _empty(cls, k: int, has_value: np.ndarray) -> "GramPostings":
        return cls(k, _EMPTY_U64, np.zeros(1, dtype=np.int64), _EMPTY_I64,
                   has_value, int(has_value.shape[0]))

    @classmethod
    def _from_pairs(cls, k: int, all_h: np.ndarray, all_pos: np.ndarray,
                    has_value: np.ndarray) -> "GramPostings":
        n = int(has_value.shape[0])
        if all_h.shape[0] == 0:
            return cls._empty(k, has_value)
        grams, offsets, positions = csr_from_pairs(all_h, all_pos)
        return cls(k, grams, offsets, positions, has_value, n)

    @classmethod
    def from_values(cls, vals: Sequence[Any], k: int) -> "GramPostings":
        """Build from python values (memtable rows, obj-kind columns):
        tokenization runs once per *distinct* string via a host cache;
        CSR assembly is pure numpy."""
        n = len(vals)
        cache: Dict[str, np.ndarray] = {}
        per_row: List[np.ndarray] = []
        has = np.zeros(n, dtype=bool)
        for i, v in enumerate(vals):
            if isinstance(v, str):
                hs = cache.get(v)
                if hs is None:
                    cache[v] = hs = value_gram_hashes(v, k)
                per_row.append(hs)
                has[i] = True
            else:
                per_row.append(_EMPTY_U64)
        counts = np.fromiter((h.shape[0] for h in per_row), np.int64,
                             count=n)
        if n == 0 or counts.sum() == 0:
            return cls._empty(k, has)
        all_h = np.concatenate(per_row)
        all_pos = np.repeat(np.arange(n, dtype=np.int64), counts)
        return cls._from_pairs(k, all_h, all_pos, has)

    @classmethod
    def from_column(cls, col: Any, k: int) -> "GramPostings":
        """Build from a dictionary-coded string column: grams are hashed
        once per dictionary value and expanded to rows by gathering code
        segments — no per-row tokenization."""
        if col.kind != "str":
            return cls.from_values(
                [v if isinstance(v, str) else None for v in col.decode()],
                k)
        flat, voffs = values_gram_hashes(col.values or [], k)
        vcounts = np.diff(voffs)
        has = col.valid.copy()
        pos = np.nonzero(col.valid)[0].astype(np.int64)
        if pos.shape[0] == 0:
            return cls._empty(k, has)
        codes = col.data[pos].astype(np.int64)
        counts = vcounts[codes]
        if int(counts.sum()) == 0:
            return cls._empty(k, has)
        return cls._from_pairs(k, _segment_gather(flat, voffs[codes],
                                                  counts),
                               np.repeat(pos, counts), has)

    @classmethod
    def from_batch(cls, batch: Any, fld: str, k: int, n_rows: int
                   ) -> "GramPostings":
        col = batch.columns.get(fld)
        if col is None:
            return cls._empty(k, np.zeros(n_rows, dtype=bool))
        return cls.from_column(col, k)

    def hit_segments(self, query_hashes: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """``[start, end)`` slices of ``positions`` holding the posting
        segments of the query grams present in this component (ascending
        and disjoint: ``query_hashes`` is sorted and distinct)."""
        if self.grams.shape[0] == 0 or query_hashes.shape[0] == 0:
            return _EMPTY_I64, _EMPTY_I64
        lo = np.searchsorted(self.grams, query_hashes, side="left")
        hi = np.searchsorted(self.grams, query_hashes, side="right")
        found = lo[hi > lo]
        return self.offsets[found], self.offsets[found + 1]

    def hit_positions(self, query_hashes: np.ndarray) -> np.ndarray:
        """Concatenated posting segments of the query grams present in
        this component: one int64 position per (query gram, row) hit,
        assembled by vectorized segment gathering (no python lists)."""
        starts, ends = self.hit_segments(query_hashes)
        if starts.shape[0] == 0:
            return _EMPTY_I64
        return _segment_gather(self.positions, starts, ends - starts)


def query_grams(spec: FuzzySpec, index_k: int) -> Tuple[np.ndarray, int]:
    """(sorted distinct query gram hashes, T-occurrence threshold) for a
    fuzzy spec against an ngram(``index_k``) index.  T <= 0 means the
    index cannot prune: every row with an indexable value is a candidate
    (the caller's ``has_value`` path).  Edit distance bounds hold for any
    gram length; a Jaccard spec whose own gram length differs from the
    index's gets no pruning (the bound would not be sound), only the
    batched verify."""
    _fld, kind, target, param = spec[:4]
    if kind == "jaccard" and spec_gram_length(spec) != index_k:
        return np.zeros(0, dtype=np.uint64), 0
    grams = sorted(set(gram_tokens(target, index_k)))
    qh = np.unique(fnv1a_hash(grams))
    if kind == "ed":
        return qh, int(qh.shape[0]) - index_k * int(param)
    if kind == "jaccard":
        deficit = len(grams) - int(qh.shape[0])
        return qh, int(math.ceil(float(param) * len(grams) - 1e-9)) - deficit
    raise ValueError(f"unknown fuzzy predicate kind {kind!r}")


class FuzzyPredicate:
    """The row-engine oracle of one fuzzy spec, carrying the spec it
    decides in normal form (``spec``: field, kind, target, param as a
    float, gram length), so a planner can tell the oracle of the
    select's own spec from any other predicate.  Non-string / absent
    values never match."""

    __slots__ = ("spec", "_target_grams")

    def __init__(self, spec: FuzzySpec):
        self.spec = _normal_spec(spec)
        _fld, kind, target, _param, k = self.spec
        if kind not in ("ed", "jaccard"):
            raise ValueError(f"unknown fuzzy predicate kind {kind!r}")
        self._target_grams = gram_tokens(target, k) \
            if kind == "jaccard" else None

    def __call__(self, r: Dict[str, Any]) -> bool:
        fld, kind, target, param, k = self.spec
        v = r.get(fld)
        if not isinstance(v, str):
            return False
        if kind == "ed":
            return edit_distance_check(v, target, param)
        return similarity_jaccard_check(gram_tokens(v, k),
                                        self._target_grams, param)

    def decides(self, spec: FuzzySpec) -> bool:
        """Is this the oracle of ``spec`` (compared in normal form)?"""
        return self.spec == _normal_spec(spec)


def _normal_spec(spec: FuzzySpec) -> Tuple[str, str, str, float, int]:
    """``spec`` as (field, kind, target, float param, gram length): the
    int and float forms of a param, and a Jaccard spec with or without
    its default gram length, compare equal."""
    fld, kind, target, param = spec[:4]
    return (fld, kind, target, float(param), spec_gram_length(spec))


def fuzzy_predicate(spec: FuzzySpec) -> FuzzyPredicate:
    """The row-engine oracle for a fuzzy spec — exactly the predicate the
    batched verification kernels reproduce, so plans can pass
    ``pred=fuzzy_predicate(spec), fuzzy=spec`` and both engines agree.
    The rewriter recognises that pairing (``FuzzyPredicate.decides``)
    and marks the fuzzy conjunct exact: the columnar chain keeps the
    batched verify's answer and re-checks no row.  A pred that wraps
    the oracle (an extra conjunct, a lambda) keeps the row re-check.
    Jaccard gram length comes from the spec (5th element, default
    GRAM_K).  Non-string / absent values never match."""
    return FuzzyPredicate(spec)
