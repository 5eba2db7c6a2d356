"""Vectorized similarity kernels for the fuzzy query subsystem.

Four entry points, numpy in / python out, following the
``columnar_ops.py`` dispatch idiom (exact pow2-padded jitted-jnp cores
under x64; on TPU the banded edit-distance DP runs as a Pallas kernel
instead, over int32 char codes with the query in SMEM; host paths below
the jax dispatch floor):

  fnv1a_hash(tokens)             vectorized FNV-1a-64 over a padded byte
                                 matrix — the one token/gram hash the
                                 ngram postings and MinHash share
  ngram_t_occurrence(tiers, ...) segmented count over device-resident
                                 per-tier postings and the query's segment
                                 bounds -> bool bitmap of rows with >= T
                                 gram hits (the ngram index candidate
                                 generator: nothing per query is shipped
                                 but the bounds)
  edit_distances(strs, q, d)     batched banded (saturating) Levenshtein
                                 DP over padded char-code matrices ->
                                 min(ed, d+1) per candidate string
  set_intersect_counts(a, b)     per-pair sorted-set intersection sizes
                                 over dictionary-coded token sets (the
                                 batched Jaccard verifier; ``jaccard_sims``
                                 derives float64 similarities)

All jnp cores pad operands to powers of two so repeated fuzzy queries
land on a bounded set of traced shapes; trace-time increments share
``columnar_ops._TRACES`` so ``ExecStats.kernel_retraces`` covers the
fuzzy cores too (repeated queries must show 0).
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import use_pallas
from . import device_pool as _pool
from .columnar_ops import LANES, _smem, _traced, pallas32
from ..obs import record_dispatch as _record_dispatch
from ..columnar.batch import pow2_len as _pow2_len

__all__ = ["fnv1a_hash", "ngram_t_occurrence",
           "edit_distances", "dictionary_edit_distances",
           "set_intersect_counts", "set_intersect_counts_padded",
           "encode_bitsets", "bitset_intersect_counts",
           "jaccard_from_counts", "jaccard_sims"]

_FNV_OFFSET = np.uint64(14695981039346656037)
_FNV_PRIME = np.uint64(1099511628211)
HOST_ED_MAX = 32   # candidate batches up to this size run the host DP


# ---------------------------------------------------------------------------
# FNV-1a token hashing (vectorized over a padded byte matrix)
# ---------------------------------------------------------------------------

def fnv1a_hash(tokens: Sequence[str]) -> np.ndarray:
    """64-bit FNV-1a of each token, bit-identical to the classic per-byte
    python loop (``data.dedup._token_hash`` before the mod): tokens are
    laid out as one [T, Lmax] byte matrix (filled with one scatter from
    their joined bytes) and the hash state advances one *column* (not one
    token) at a time, so the python-level work is O(max token length),
    not O(total bytes)."""
    n = len(tokens)
    if n == 0:
        return np.zeros(0, dtype=np.uint64)
    bs = [t.encode() for t in tokens]
    lens = np.fromiter(map(len, bs), dtype=np.int64, count=n)
    lmax = int(lens.max())
    mat = np.zeros((n, max(lmax, 1)), dtype=np.uint64)
    flat = np.frombuffer(b"".join(bs), dtype=np.uint8)
    if flat.size:
        rows = np.repeat(np.arange(n), lens)
        starts = np.cumsum(lens) - lens
        mat[rows, np.arange(flat.size) - np.repeat(starts, lens)] = flat
    h = np.full(n, _FNV_OFFSET, dtype=np.uint64)
    for j in range(lmax):
        live = j < lens
        hj = (h ^ mat[:, j]) * _FNV_PRIME          # uint64 wrap == mod 2**64
        h = np.where(live, hj, h)
    return h


# ---------------------------------------------------------------------------
# T-occurrence segmented count (ngram candidate generation)
# ---------------------------------------------------------------------------

def _tocc_tier_math(cnt, pos, starts, cum, nhits, off, hits_p2):
    """Add one storage tier's query-gram hits into the running count.
    Hit ``k`` (of ``nhits``) lies in segment ``g`` — the last whose
    exclusive length prefix ``cum[g]`` is <= k — at ``starts[g] + k -
    cum[g]`` of the tier's resident positions; it scatter-adds at its row
    ``+ off`` of the storage concat.  Lanes past ``nhits`` (pow2 padding)
    land in the sentinel slot, the count's last.  Jit-agnostic: traced by
    :func:`_tocc_tier_core` (one tier a dispatch) and by
    ``runtime/spmd._tocc_fn`` (one tier slot of every partition of a
    shard, flattened into one count)."""
    k = jnp.arange(hits_p2, dtype=jnp.int64)
    g = jnp.searchsorted(cum, k, side="right") - 1
    live = k < nhits
    idx = jnp.where(live, starts[g] + k - cum[g], 0)
    sentinel = cnt.shape[0] - 1
    return cnt.at[jnp.where(live, pos[idx] + off, sentinel)].add(1)


@functools.partial(jax.jit, static_argnums=(6,))
def _tocc_tier_core(cnt, pos, starts, cum, nhits, off, hits_p2):
    _traced()
    return _tocc_tier_math(cnt, pos, starts, cum, nhits, off, hits_p2)


@jax.jit
def _tocc_finish(cnt, thr):
    _traced()
    return cnt[:-1] >= thr


def ngram_t_occurrence(tiers: Sequence[Any], total: int,
                       threshold: int) -> np.ndarray:
    """Bool [total] over the storage concat: rows whose query-gram hit
    count reaches ``threshold``.  ``tiers`` are ``(offset, positions,
    padded_positions, starts, ends)`` per storage tier, ``starts/ends``
    the query grams' posting segments (``GramPostings.hit_segments``).

    The device path ships nothing per query but the segment bounds (a
    query constant, like range bounds): the padded positions arrays are
    pool keys for their postings' lifetime.  Each tier gathers only its
    hit segments (work O(hits), pow2-padded) into one device-resident
    count, so programs are keyed by pow2 buckets, not by the tier list.
    Below the jax dispatch floor the segments gather and count on the
    host.  Under a partition mesh ``runtime/spmd.batched_t_occurrence``
    counts every partition's tiers in one dispatch with the same math."""
    hits = [int((en - st).sum()) for _o, _p, _pp, st, en in tiers]
    if sum(hits) == 0:
        return np.zeros(total, dtype=bool)
    if total + sum(hits) <= 4096:
        from ..columnar.postings import segment_gather
        pos = np.concatenate([off + segment_gather(p, st, en - st)
                              for off, p, _pp, st, en in tiers])
        return np.bincount(pos, minlength=total) >= threshold
    live = [(t, h) for t, h in zip(tiers, hits) if h]
    ops, missed = _pool.fetch([t[2] for t, _h in live])
    with jax.enable_x64(True):
        cnt = jnp.zeros(_pow2_len(total) + 1, dtype=jnp.int32)
        for ((off, _p, _pp, st, en), h), pos in zip(live, ops):
            gp = _pow2_len(st.shape[0])
            starts = np.zeros(gp, dtype=np.int64)
            starts[:st.shape[0]] = st
            # exclusive length prefix; padding segments start past every hit
            cum = np.full(gp, np.iinfo(np.int64).max, dtype=np.int64)
            cum[0] = 0
            cum[1:st.shape[0]] = np.cumsum(en - st)[:-1]
            cnt = _tocc_tier_core(cnt, pos, starts, cum, np.int64(h),
                                  np.int64(off), _pow2_len(h))
        mask = np.asarray(_tocc_finish(cnt, np.int32(threshold)))
    _record_dispatch("ngram_t_occurrence", h2d=missed, d2h=[mask])
    return mask[:total]


# ---------------------------------------------------------------------------
# batched banded edit distance (candidate verification)
# ---------------------------------------------------------------------------

def _cummin_last(t, big):
    """Cumulative min along the last axis via log-step shifts (works in
    both the jnp core and the Pallas kernel body; shapes stay static)."""
    n = t.shape[-1]
    s = 1
    while s < n:
        shifted = jnp.concatenate(
            [jnp.full(t.shape[:-1] + (s,), big, t.dtype), t[..., :-s]],
            axis=-1)
        t = jnp.minimum(t, shifted)
        s *= 2
    return t


@jax.jit
def _ed_core(cand, lens, q, qlen, d):
    """Saturating Levenshtein DP, vectorized over the candidate batch.

    One DP row per query char; the within-row min-plus recurrence
    ``new[j] = min(m[j], new[j-1]+1)`` collapses to a cumulative min of
    ``m[j]-j`` (the +1-per-step factors out), so every step is dense
    [B, L+1] arithmetic.  Values saturate at ``d+1`` (the band): cells
    beyond the band can only produce answers > d, so clamping them keeps
    the <= d decision exact and the final value equal to min(ed, d+1).
    """
    _traced()
    B, L = cand.shape
    M = q.shape[0]
    cap = (d + 1).astype(jnp.int64)
    j = jnp.arange(L + 1, dtype=jnp.int64)
    dp = jnp.minimum(j, cap) * jnp.ones((B, 1), dtype=jnp.int64)
    big = jnp.asarray(1 << 30, jnp.int64)

    def body(i, dp):
        qc = q[jnp.minimum(i, M - 1)]
        sub = (cand != qc).astype(jnp.int64)                     # [B, L]
        m_ = jnp.concatenate(
            [jnp.full((B, 1), 1, jnp.int64) + i,
             jnp.minimum(dp[:, 1:] + 1, dp[:, :-1] + sub)], axis=1)
        t = _cummin_last(m_ - j[None, :], big)
        new = jnp.minimum(t + j[None, :], cap)
        return jnp.where(i < qlen, new, dp)

    dp = jax.lax.fori_loop(0, M, body, dp)
    pick = jnp.minimum(lens, L)
    onehot = j[None, :] == pick[:, None]
    return jnp.sum(jnp.where(onehot, dp, 0), axis=1)


def _char_matrix(strings: Sequence[str], width: int, rows: int
                 ) -> np.ndarray:
    mat = np.zeros((rows, width), dtype=np.int32)
    for i, s in enumerate(strings):
        if s:
            mat[i, :len(s)] = np.fromiter(map(ord, s), dtype=np.int32,
                                          count=len(s))
    return mat


def _query_codes(query: str) -> np.ndarray:
    """The query's code points, zero-padded to a pow2 length (a query
    constant: it rides as a kernel argument, never through the pool)."""
    q = np.zeros(_pow2_len(max(len(query), 1)), dtype=np.int32)
    if query:
        q[:len(query)] = np.fromiter(map(ord, query), dtype=np.int32,
                                     count=len(query))
    return q


def _ed_operands(strings: Sequence[str], pallas: bool) -> tuple:
    """(candidate char matrix, lengths) in the layout of the chosen core:
    [B, L] rows for the jnp core, [L + 1, B] lanes (row 0 a sentinel) for
    the Pallas kernel; batch and width pow2-padded."""
    B = len(strings)
    lens = np.fromiter((len(s) for s in strings), np.int64, count=B)
    width = int(lens.max()) if B else 0
    if not pallas:
        bp = _pow2_len(B)
        cand = _char_matrix(strings, _pow2_len(max(width, 1)), bp)
        return cand, np.concatenate([lens, np.zeros(bp - B, np.int64)])
    bp = max(LANES, _pow2_len(B))
    rows = max(8, _pow2_len(width + 1))
    cand = np.full((rows, bp), -1, dtype=np.int32)
    cand[1:, :] = _char_matrix(strings, rows - 1, bp).T
    lv = np.zeros((1, bp), dtype=np.int32)
    lv[0, :B] = lens
    return cand, lv


def _ed_run(cand: np.ndarray, lens: np.ndarray, B: int, query: str, d: int,
            pallas: bool, interpret: bool = False) -> np.ndarray:
    q = _query_codes(query)
    ops, missed = _pool.fetch([cand, lens])
    with jax.enable_x64(True):
        if pallas:
            params = np.asarray([len(query), d + 1], dtype=np.int32)
            out = _ed_pallas_core(ops[0], ops[1], q, params,
                                  interpret=interpret)
        else:
            out = _ed_core(ops[0], ops[1], q,
                           np.asarray(len(query), np.int64),
                           np.asarray(d, np.int64))
        out = np.asarray(out)
    _record_dispatch("edit_distances", h2d=missed, d2h=[out], pallas=pallas)
    return out[:B].astype(np.int64)


def _ed_jnp(strings: Sequence[str], query: str, d: int) -> np.ndarray:
    return _ed_run(*_ed_operands(strings, False), len(strings), query, d,
                   False)


def _shift_rows(x, s, fill):
    """``x`` shifted ``s`` rows down along the sublane axis (row j holds
    old row j - s; the first ``s`` rows take ``fill``)."""
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(row >= s, pltpu.roll(x, s, 0), fill)


def _ed_kernel(p_ref, q_ref, c_ref, l_ref, o_ref):
    """The banded DP of ``_ed_core`` with the batch on lanes: a tile is
    [Lp, 128] — DP column j on sublanes, 128 candidates on lanes — so
    the within-row min-plus recurrence is a log-step cummin down the
    sublanes (rolls), and the answer row is lane-dense.  Row 0 of
    ``c_ref`` is a sentinel; row j >= 1 holds each candidate's char j-1.
    ``p_ref`` holds (query length, cap = d + 1); ``q_ref`` the query."""
    cand = c_ref[...]
    rows = cand.shape[0]
    jrow = jax.lax.broadcasted_iota(jnp.int32, cand.shape, 0)
    cap = p_ref[1]
    big = jnp.int32(1 << 30)

    def body(i, dp):
        sub = (cand != q_ref[i]).astype(jnp.int32)
        m_ = jnp.minimum(dp + 1, _shift_rows(dp, 1, big) + sub)
        m_ = jnp.where(jrow == 0, i + 1, m_)
        t = m_ - jrow
        s = 1
        while s < rows:
            t = jnp.minimum(t, _shift_rows(t, s, big))
            s *= 2
        return jnp.minimum(t + jrow, cap)

    dp = jax.lax.fori_loop(0, p_ref[0], body, jnp.minimum(jrow, cap))
    pick = jnp.minimum(l_ref[...], rows - 1)             # [1, 128]
    o_ref[...] = jnp.sum(jnp.where(jrow == pick, dp, 0), axis=0,
                         keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ed_pallas_core(cand, lens, q, params, interpret=False):
    _traced()
    rows, bp = cand.shape
    col = pl.BlockSpec((rows, LANES), lambda i: (0, i))
    lane = pl.BlockSpec((1, LANES), lambda i: (0, i))
    return pallas32(
        _ed_kernel, params, q, cand, lens,
        grid=(bp // LANES,),
        in_specs=[_smem(), _smem(), col, lane],
        out_specs=lane,
        out_shape=jax.ShapeDtypeStruct((1, bp), jnp.int32),
        interpret=interpret)[0]


def _ed_pallas(strings: Sequence[str], query: str, d: int,
               *, interpret: bool = False) -> np.ndarray:
    # pow2-padded batch, width AND query length, so distinct queries of
    # similar length share one compilation; the loop runs to the true
    # query length (an SMEM scalar)
    return _ed_run(*_ed_operands(strings, True), len(strings), query, d,
                   True, interpret)


def edit_distances(strings: Sequence[str], query: str, d: int,
                   *, force_pallas: Optional[bool] = None,
                   interpret: bool = False) -> np.ndarray:
    """``min(edit_distance(s, query), d+1)`` per candidate: saturated
    (banded) distances whose ``<= d`` decision is exact.  Char codes are
    int32-exact on the Pallas path (max code point 0x10FFFF); a tiny
    batch runs the host DP outright on every backend (one dispatch, and
    shipping the batch, cost more).
    """
    B = len(strings)
    if B == 0:
        return np.zeros(0, dtype=np.int64)
    d = max(int(d), 0)
    if B <= HOST_ED_MAX:
        from ..core.functions import edit_distance
        return np.asarray([min(edit_distance(s, query), d + 1)
                           for s in strings], dtype=np.int64)
    pallas = use_pallas() if force_pallas is None else force_pallas
    if pallas:
        return _ed_pallas(strings, query, d, interpret=interpret)
    return _ed_jnp(strings, query, d)


def dictionary_edit_distances(owner: Any, values: Sequence[str], query: str,
                              d: int, *, force_pallas: Optional[bool] = None,
                              interpret: bool = False) -> np.ndarray:
    """``edit_distances`` over a whole immutable string dictionary
    (``values``, owned by ``owner`` — a component column): the char
    matrix is built once per owner and layout, so it is a stable pool key
    and stays device-resident; a repeated query ships only its own
    characters.  Verification of many candidates runs here rather than
    over per-query candidate batches."""
    d = max(int(d), 0)
    pallas = use_pallas() if force_pallas is None else force_pallas
    cand, lens = _pool.memo(owner, "ed_pallas" if pallas else "ed_jnp",
                            lambda _o: _ed_operands(values, pallas))
    return _ed_run(cand, lens, len(values), query, d, pallas, interpret)


# ---------------------------------------------------------------------------
# batched sorted-set intersection (Jaccard verification)
# ---------------------------------------------------------------------------

@jax.jit
def _inter_core(a, alens, b):
    """Per-pair |A ∩ B| via a vmapped binary search of each A element in
    the (sorted, sentinel-padded) B row."""
    _traced()
    s1 = a.shape[1]

    def row(ar, al, br):
        pos = jnp.searchsorted(br, ar)
        posc = jnp.clip(pos, 0, br.shape[0] - 1)
        hit = (br[posc] == ar) & (jnp.arange(s1) < al)
        return jnp.sum(hit)

    return jax.vmap(row)(a, alens, b)


def _inter_jnp(a_mat, alens, b_mat) -> np.ndarray:
    ops, missed = _pool.fetch([a_mat, alens, b_mat])
    with jax.enable_x64(True):
        out = np.asarray(_inter_core(ops[0], ops[1], ops[2]))
    _record_dispatch("set_intersect_counts", h2d=missed, d2h=[out])
    return out


_SENTINEL = np.int64(np.iinfo(np.int64).max)


def _pad_sets(sets: Sequence[np.ndarray], fill: np.int64
              ) -> tuple:
    P = len(sets)
    lens = np.zeros(_pow2_len(max(P, 1)), dtype=np.int64)
    lens[:P] = np.fromiter((len(s) for s in sets), np.int64, count=P)
    width = _pow2_len(max(int(lens.max()) if P else 0, 1))
    mat = np.full((lens.shape[0], width), fill, dtype=np.int64)
    for i, s in enumerate(sets):
        if len(s):
            mat[i, :len(s)] = s
    return mat, lens, lens.shape[0]


def set_intersect_counts_padded(a_mat: np.ndarray, alens: np.ndarray,
                                b_mat: np.ndarray, blens: np.ndarray
                                ) -> np.ndarray:
    """Pre-padded variant of ``set_intersect_counts`` for callers that
    gather pair rows out of one shared record matrix (FuzzyJoin verify:
    pad each record once, then every candidate pair is a fancy-index —
    no per-pair python assembly).  ``b_mat`` rows must be sorted with the
    int64 sentinel as padding; ``a_mat`` rows are masked by ``alens``."""
    P = int(a_mat.shape[0])
    if P == 0:
        return np.zeros(0, dtype=np.int64)
    pp = _pow2_len(P)
    if pp != P:             # pow2 row padding keeps the jit shapes stable
        a2 = np.zeros((pp, a_mat.shape[1]), dtype=np.int64)
        a2[:P] = a_mat
        b2 = np.full((pp, b_mat.shape[1]), _SENTINEL, dtype=np.int64)
        b2[:P] = b_mat
        l2 = np.zeros(pp, dtype=np.int64)
        l2[:P] = alens
        a_mat, b_mat, alens = a2, b2, l2
    if P <= 16:
        return np.asarray(
            [len(np.intersect1d(a_mat[i][:alens[i]],
                                b_mat[i][:blens[i]], assume_unique=True))
             for i in range(P)], dtype=np.int64)
    return _inter_jnp(a_mat, alens, b_mat)[:P]


def set_intersect_counts(a_sets: Sequence[np.ndarray],
                         b_sets: Sequence[np.ndarray]) -> np.ndarray:
    """``|a_sets[i] & b_sets[i]|`` per pair.  Each set is a sorted array
    of distinct dictionary codes; the b side pads with an int64 sentinel
    (stays sorted) and the a side is masked by its true length, so pads
    never match.  The jnp core is exact int64 on every backend: the
    pairs are per-query data shipped with every call, so there is no
    resident operand for a TPU kernel to reuse."""
    P = len(a_sets)
    if P == 0:
        return np.zeros(0, dtype=np.int64)
    a_mat, alens, _ = _pad_sets(a_sets, np.int64(0))
    b_mat, blens, _ = _pad_sets(b_sets, _SENTINEL)
    return set_intersect_counts_padded(a_mat[:P], alens[:P], b_mat[:P],
                                       blens[:P])


@jax.jit
def _popcount_inter_core(bits, ai, bi):
    """Per-pair |A ∩ B| over vocabulary bitsets, gather fused in: both
    pair rows are gathered from the one shared record matrix on-device,
    then AND + popcount row-reduce (XLA ``population_count`` vectorizes
    on every backend, TPU included, so this core needs no separate
    Pallas variant)."""
    _traced()
    return jnp.sum(jax.lax.population_count(bits[ai] & bits[bi]), axis=1)


def encode_bitsets(codes: np.ndarray, seg: np.ndarray, n_rows: int,
                   vocab_size: int) -> np.ndarray:
    """[n_rows, W] uint32 vocabulary bitsets from (row segment, code)
    pairs — the dense-dictionary fast path for pairwise set intersection
    when the vocabulary is small enough that a record is a few machine
    words.  Build is pure numpy: one argsort + one ``bitwise_or.reduceat``
    over the (row, word) groups, no per-token python loop."""
    W = _pow2_len(max((vocab_size + 31) // 32, 1))
    bits = np.zeros(n_rows * W, dtype=np.uint32)
    if codes.shape[0]:
        keys = seg.astype(np.int64) * W + (codes >> 5)
        vals = np.left_shift(np.uint32(1),
                             (codes & 31).astype(np.uint32))
        order = np.argsort(keys, kind="stable")
        keys, vals = keys[order], vals[order]
        # keys are sorted: group starts come from one diff, not a resort
        starts = np.flatnonzero(np.concatenate(
            [np.ones(1, dtype=bool), keys[1:] != keys[:-1]]))
        bits[keys[starts]] = np.bitwise_or.reduceat(vals, starts)
    return bits.reshape(n_rows, W)


def bitset_intersect_counts(bits: np.ndarray, ai: np.ndarray,
                            bi: np.ndarray) -> np.ndarray:
    """``popcount(bits[ai[p]] & bits[bi[p]])`` per pair (int64): the
    record matrix crosses to the device once; pair gathers happen inside
    the jitted core.  Index arrays pad to pow2 with row 0 (sliced off),
    keeping the traced shapes stable as the candidate count varies."""
    P = int(ai.shape[0])
    if P == 0:
        return np.zeros(0, dtype=np.int64)
    pp = _pow2_len(P)
    if pp != P:
        ai = np.concatenate([ai, np.zeros(pp - P, dtype=np.int64)])
        bi = np.concatenate([bi, np.zeros(pp - P, dtype=np.int64)])
    rp = _pow2_len(max(int(bits.shape[0]), 1))
    if rp != bits.shape[0]:
        bits = np.concatenate(
            [bits, np.zeros((rp - bits.shape[0], bits.shape[1]),
                            dtype=np.uint32)])
    # the record bitset matrix is reused across outer batches of a fuzzy
    # join: pooling it means only the per-batch index arrays re-ship
    ops, missed = _pool.fetch([bits, ai, bi])
    out = np.asarray(_popcount_inter_core(ops[0], ops[1], ops[2]))
    _record_dispatch("bitset_intersect_counts", h2d=missed, d2h=[out])
    return out[:P].astype(np.int64)


def jaccard_from_counts(inter: np.ndarray, a_sizes: np.ndarray,
                        b_sizes: np.ndarray) -> np.ndarray:
    """Finish Jaccard from intersection counts in float64 — the one
    place the division and the two-empty-sets -> 1.0 convention live, so
    every batched path matches the scalar ``similarity_jaccard`` oracle
    bit-for-bit."""
    inter = inter.astype(np.float64)
    union = a_sizes + b_sizes - inter
    return np.where(union > 0, inter / np.maximum(union, 1.0), 1.0)


def jaccard_sims(a_sets: Sequence[np.ndarray], b_sets: Sequence[np.ndarray]
                 ) -> np.ndarray:
    """Exact float64 Jaccard similarity per pair of dictionary-coded
    sets (intersection counted by the kernel, the division done host-
    side so decisions match the python ``len(&)/len(|)`` oracle)."""
    inter = set_intersect_counts(a_sets, b_sets)
    al = np.fromiter((len(s) for s in a_sets), np.float64,
                     count=len(a_sets))
    bl = np.fromiter((len(s) for s in b_sets), np.float64,
                     count=len(b_sets))
    return jaccard_from_counts(inter, al, bl)
