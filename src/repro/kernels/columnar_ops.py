"""Fused columnar predicate + reduction kernels (the columnar engine's
hot path).

Four entry points, numpy in / python out, mirroring the ``ops.py``
backend-dispatch idiom:

  range_mask(preds)              conjunctive [lo, hi] range predicate over
                                 K columns -> bool mask
  fused_filter_aggregate(...)    the same mask fused with count/sum/min/max
                                 reductions over M aggregate columns in one
                                 pass (no materialized mask, no gather)
  sorted_intersect_mask(...)     sorted PK candidate set vs a partition's
                                 sorted live-pk array -> position bitmap
                                 (the columnar index access path: bitmaps
                                 intersect before any record is gathered)
  sorted_merge_take(...)         k-way sorted-PK merge/dedup/tombstone-drop
                                 -> take indices into the concatenated
                                 inputs (the LSM merge path: every column
                                 of the merged component is one gather)

Every operator has one exact answer and two device implementations,
chosen by what the input holds: the jitted jnp core runs in the column's
native dtype under x64 (exact for int64 epoch-microsecond, dictionary-
code and float64 columns; XLA compiles it for every backend), and on TPU
a Pallas kernel runs instead when every operand is exact in 32-bit
integer lanes — integer columns whose values (memoized per immutable
array) and bounds fit int32, and, where the caller reads a column's sum,
an absolute sum that fits int32, so no partial sum can wrap.  Float columns always take the
x64 core.  Both implementations read the same pooled, pow2-padded device
operands (``kernels/device_pool``); the 32-bit narrowing happens on the
device, so a warm query ships nothing either way.

All entry points pad their operands to the next power of two (invalid
rows, so results are unchanged) before hitting the jitted cores:
repeated scans/merges over growing or gathered batches land on a bounded
set of traced shapes instead of retracing per length.  ``trace_count()``
exposes the cumulative number of traces so callers (ExecStats) can assert
zero retraces on repeated queries.
"""

from __future__ import annotations

import functools
import heapq
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import use_pallas
from . import device_pool as _pool
from ..obs import record_dispatch as _record_dispatch
from ..obs import record_retrace as _record_retrace
# the canonical pow2 helper lives with the padded-column storage it
# shapes (no cycle: columnar/__init__ pulls batch+schema only, and
# batch.py imports this module lazily)
from ..columnar.batch import pow2_len as _pow2_len
from ..columnar.batch import promotes_lossless as _promotes_lossless

__all__ = ["range_mask", "fused_filter_aggregate", "sorted_intersect_mask",
           "sorted_merge_take", "trace_count"]

# Cumulative number of jit traces of the columnar cores.  The increments
# below run at *trace* time only (python side effects inside jitted
# functions), so ``trace_count()`` deltas expose retraces: a repeated
# query over pow2-padded operands must not move this counter.
_TRACES = {"n": 0}


def trace_count() -> int:
    return _TRACES["n"]


def _traced() -> None:
    _TRACES["n"] += 1
    _record_retrace()

# (data [N], valid [N] bool, lo, hi) — already in the column's physical
# (numeric) domain; None bound means unbounded on that side.
Pred = Tuple[np.ndarray, np.ndarray, Any, Any]

LANES = 128            # Pallas operands are [rows, 128] lane-dense tiles
_ROW_BLOCK = 256       # rows per grid step (256 x 128 int32 = 128 KiB)
SMEM_CAP = 4096        # scalar-loop operands (candidate keys, postings
                       # positions) held whole in SMEM; 1 MiB on v5e
_I32 = np.iinfo(np.int32)


def _row_grid(np2: int) -> Tuple[int, int]:
    """(rows, rows per block) of a length-``np2`` operand viewed as
    [rows, 128]; ``np2`` is a power of two >= 128, so the block divides
    the rows and is either a multiple of 8 or the whole array."""
    rows = np2 // LANES
    return rows, min(rows, _ROW_BLOCK)


def _lane_block(rb: int) -> Any:
    return pl.BlockSpec((rb, LANES), lambda i: (i, 0))


def _smem() -> Any:
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def pallas32(kernel: Any, *args: Any, **call: Any) -> Any:
    """``pl.pallas_call(kernel, **call)(*args)`` traced with x64 off: the
    callers run under x64 (their pooled operands are int64), but Mosaic
    needs 32-bit grid indices and loop counters.  Operands are narrowed
    to 32 bits before the call."""
    with jax.enable_x64(False):
        return pl.pallas_call(kernel, **call)(*args)


# ---------------------------------------------------------------------------
# jnp cores (exact: native dtype under x64; jitted so one query costs one
# dispatch per partition, not one per column op)
# ---------------------------------------------------------------------------

def _prep_bounds(data: np.ndarray, lo: Any, hi: Any
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Same-dtype 0-d bound arrays (unbounded -> dtype extremes) so the
    jitted core never mixes int64 with float infinities."""
    if np.issubdtype(data.dtype, np.integer):
        info = np.iinfo(data.dtype)
        return (np.asarray(info.min if lo is None else lo, data.dtype),
                np.asarray(info.max if hi is None else hi, data.dtype))
    return (np.asarray(-np.inf if lo is None else lo, data.dtype),
            np.asarray(np.inf if hi is None else hi, data.dtype))


@jax.jit
def _mask_core(datas, valids, los, his):
    _traced()
    return _mask_expr(datas, valids, los, his)


def _mask_expr(datas, valids, los, his):
    m = None
    for x, v, lo, hi in zip(datas, valids, los, his):
        mm = v & (x >= lo) & (x <= hi)
        m = mm if m is None else (m & mm)
    return m


def _ident(dtype, is_min: bool):
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return jnp.asarray(info.max if is_min else info.min, dtype)
    return jnp.asarray(jnp.inf if is_min else -jnp.inf, dtype)


@jax.jit
def _agg_core(datas, valids, los, his, agg_datas, agg_valids):
    _traced()
    mask = _mask_expr(datas, valids, los, his)
    total = jnp.sum(mask)
    per_col = []
    for x, v in zip(agg_datas, agg_valids):
        ok = mask & v
        cnt = jnp.sum(ok)
        s = jnp.sum(jnp.where(ok, x, jnp.asarray(0, x.dtype)))
        mn = jnp.min(jnp.where(ok, x, _ident(x.dtype, True)))
        mx = jnp.max(jnp.where(ok, x, _ident(x.dtype, False)))
        per_col.append((s, mn, mx, cnt))
    return total, tuple(per_col)


def _split_preds(preds: Sequence[Pred]):
    datas = tuple(p[0] for p in preds)
    valids = tuple(p[1] for p in preds)
    bounds = [_prep_bounds(p[0], p[2], p[3]) for p in preds]
    los = tuple(b[0] for b in bounds)
    his = tuple(b[1] for b in bounds)
    return datas, valids, los, his


def _pad_pred(p: Pred, np2: int) -> Pred:
    """Length-pad one predicate column to ``np2`` with invalid rows: the
    row-validity conjunct keeps padding out of the mask, so results are
    identical while the jitted cores see a bounded set of shapes."""
    data, valid, lo, hi = p
    pad = np2 - data.shape[0]
    if pad <= 0:
        return p
    return (np.concatenate([data, np.zeros(pad, dtype=data.dtype)]),
            np.concatenate([valid, np.zeros(pad, dtype=bool)]), lo, hi)


@functools.lru_cache(maxsize=64)
def _live_pred(n: int, np2: int) -> Pred:
    """Unbounded predicate whose validity bitmap is the row-liveness flag
    (True for the first ``n`` rows): ANDing it in masks padding out.
    Memoized per (n, np2) bucket so repeated no-predicate aggregates hand
    the buffer pool the same arrays instead of re-allocating per query."""
    live = np.zeros(np2, dtype=bool)
    live[:n] = True
    return (np.zeros(np2, dtype=np.int64), live, None, None)


def _padded_preds(preds: Sequence[Pred], n: int, np2: int) -> List[Pred]:
    """Predicates padded to ``np2``; with none, the liveness predicate
    (padding rows must never count)."""
    if preds:
        return [_pad_pred(p, np2) for p in preds]
    return [_live_pred(n, np2)]


# ---------------------------------------------------------------------------
# Pallas kernels (TPU): per-column [rows, 128] int32 tiles narrowed on the
# device from the pooled operands; bounds read from SMEM
# ---------------------------------------------------------------------------

def _i32_bounds(preds: Sequence[Pred], np2: int) -> Optional[np.ndarray]:
    """The predicates' [lo0, hi0, lo1, hi1, ...] int32 bounds when the
    Pallas kernel is exact for them, else None.  Bounds go through the
    jnp core's own conversion (``_prep_bounds``), so both paths decide
    every row identically; a bound past the int32 range clamps to it,
    which is exact because every value is inside."""
    if np2 % LANES:
        return None
    out: List[int] = []
    for data, _valid, lo, hi in preds:
        if not _pool.fits_int32(data):
            return None
        l, h = (int(b) for b in _prep_bounds(data, lo, hi))
        if l > _I32.max or h < _I32.min:
            return None                 # empty range: leave it to the core
        out += [max(l, int(_I32.min)), min(h, int(_I32.max))]
    return np.asarray(out, dtype=np.int32)


def _tiles(arrs, rows: int):
    return [a.astype(jnp.int32).reshape(rows, LANES) for a in arrs]


def _mask_tile(b_ref, x_refs, v_refs):
    m = None
    for j, (x_ref, v_ref) in enumerate(zip(x_refs, v_refs)):
        x = x_ref[...]
        mm = (v_ref[...] != 0) & (x >= b_ref[2 * j]) & (x <= b_ref[2 * j + 1])
        m = mm if m is None else (m & mm)
    return m


def _mask_kernel(b_ref, *refs, k):
    o_ref = refs[2 * k]
    o_ref[...] = _mask_tile(b_ref, refs[:k], refs[k:2 * k]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _mask_pallas_core(datas, valids, bounds, interpret=False):
    _traced()
    k = len(datas)
    rows, rb = _row_grid(datas[0].shape[0])
    out = pallas32(
        functools.partial(_mask_kernel, k=k),
        bounds, *_tiles(datas, rows), *_tiles(valids, rows),
        grid=(rows // rb,),
        in_specs=[_smem()] + [_lane_block(rb)] * (2 * k),
        out_specs=_lane_block(rb),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        interpret=interpret)
    return out.reshape(-1) != 0


def _tile_reduce(fn, x):
    """[1, 1] reduction of a tile, one axis at a time: Mosaic lowers a
    reduction to a 0-d scalar through jnp, which widens to 64 bits when
    the caller traces under x64."""
    return fn(fn(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _tile_sum(x):
    return _tile_reduce(jnp.sum, x)


def _agg_kernel(b_ref, *refs, k, m):
    """Grid-accumulated [8, 128] int32 block: row 0 sums, 1 mins, 2 maxs,
    3 valid-survivor counts (lane j = aggregate column j), row 4 lane 0
    the predicate survivor count.  Rows are assembled with lane-iota
    selects (no scatter), and merged into the resident output block with
    a row-iota select between add / min / max."""
    x_refs, v_refs = refs[:k], refs[k:2 * k]
    a_refs, av_refs = refs[2 * k:2 * k + m], refs[2 * k + m:2 * k + 2 * m]
    o_ref = refs[2 * k + 2 * m]
    mask = _mask_tile(b_ref, x_refs, v_refs)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    hi = jnp.int32(_I32.max)
    lo = jnp.int32(_I32.min)
    sums = jnp.zeros((1, LANES), jnp.int32)
    mins = jnp.full((1, LANES), hi, jnp.int32)
    maxs = jnp.full((1, LANES), lo, jnp.int32)
    cnts = jnp.zeros((1, LANES), jnp.int32)
    for j in range(m):
        a = a_refs[j][...]
        ok = mask & (av_refs[j][...] != 0)
        at = lane == j
        sums = jnp.where(at, _tile_sum(jnp.where(ok, a, 0)), sums)
        mins = jnp.where(at, _tile_reduce(jnp.min, jnp.where(ok, a, hi)),
                         mins)
        maxs = jnp.where(at, _tile_reduce(jnp.max, jnp.where(ok, a, lo)),
                         maxs)
        cnts = jnp.where(at, _tile_sum(ok.astype(jnp.int32)), cnts)
    total = jnp.where(lane == 0, _tile_sum(mask.astype(jnp.int32)), 0)
    pad = jnp.zeros((3, LANES), jnp.int32)
    upd = jnp.concatenate([sums, mins, maxs, cnts, total, pad], axis=0)
    row = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.where(row == 1, hi, jnp.where(row == 2, lo, 0))

    s = o_ref[...]
    o_ref[...] = jnp.where(row == 1, jnp.minimum(s, upd),
                           jnp.where(row == 2, jnp.maximum(s, upd), s + upd))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _agg_pallas_core(datas, valids, bounds, agg_datas, agg_valids,
                     interpret=False):
    _traced()
    k, m = len(datas), len(agg_datas)
    rows, rb = _row_grid(datas[0].shape[0])
    return pallas32(
        functools.partial(_agg_kernel, k=k, m=m),
        bounds, *_tiles(datas, rows), *_tiles(valids, rows),
        *_tiles(agg_datas, rows), *_tiles(agg_valids, rows),
        grid=(rows // rb,),
        in_specs=[_smem()] + [_lane_block(rb)] * (2 * k + 2 * m),
        out_specs=pl.BlockSpec((8, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, LANES), jnp.int32),
        interpret=interpret)


def _agg_i32_exact(aggs: Sequence[Tuple[np.ndarray, np.ndarray]],
                   summed: Sequence[bool]) -> bool:
    """Every aggregate column fits int32 lanes (count/min/max are then
    exact); a column whose sum is read must also have an absolute sum (a
    bound on every partial sum) that fits, so no partial sum can wrap."""
    return len(aggs) <= LANES and all(
        _pool.fits_int32(a) and (not s or _pool.abs_sum(a) <= _I32.max)
        for (a, _v), s in zip(aggs, summed))


# ---------------------------------------------------------------------------
# range mask / fused aggregate dispatch
# ---------------------------------------------------------------------------

def _mask_dispatch(preds: Sequence[Pred], n: int, pallas: bool,
                   interpret: bool) -> np.ndarray:
    """Operand arrays may already carry a pow2-padded tail of invalid
    rows (``columnar.batch.Column.padded``); anything shorter is padded
    here so the jitted cores only ever see pow2 shapes."""
    np2 = max(_pow2_len(n), max(int(p[0].shape[0]) for p in preds))
    preds = _padded_preds(preds, n, np2)
    datas, valids, los, his = _split_preds(preds)
    bounds = _i32_bounds(preds, np2) if pallas else None
    # already-resident operands (pooled component views) ship nothing;
    # only this call's actual uploads count as h2d
    k = len(datas)
    ops, missed = _pool.fetch(list(datas) + list(valids))
    with jax.enable_x64(True):
        if bounds is not None:
            out = _mask_pallas_core(tuple(ops[:k]), tuple(ops[k:]), bounds,
                                    interpret=interpret)
        else:
            out = _mask_core(tuple(ops[:k]), tuple(ops[k:]), los, his)
        out = np.asarray(out)
    _record_dispatch("range_mask", h2d=missed, d2h=[out],
                     pallas=bounds is not None)
    return out[:n]


def _agg_dispatch(preds: Sequence[Pred],
                  aggs: Sequence[Tuple[np.ndarray, np.ndarray]],
                  n: int, pallas: bool, interpret: bool,
                  summed: Sequence[bool]) -> Dict[str, Any]:
    if not aggs:
        mask = _mask_dispatch(preds, n, pallas, interpret) if preds \
            else np.ones(n, dtype=bool)
        return {"count": int(mask.sum()), "sums": [], "mins": [],
                "maxs": [], "cnts": []}
    np2 = max([_pow2_len(n)] + [int(a[0].shape[0]) for a in aggs]
              + [int(p[0].shape[0]) for p in preds])
    # padded rows are invalid in every conjunct (or masked by liveness)
    preds = _padded_preds(preds, n, np2)
    padded_aggs = []
    for data, valid in aggs:
        pad = np2 - data.shape[0]
        if pad > 0:
            data = np.concatenate([data, np.zeros(pad, dtype=data.dtype)])
            valid = np.concatenate([valid, np.zeros(pad, dtype=bool)])
        padded_aggs.append((data, valid))
    datas, valids, los, his = _split_preds(preds)
    bounds = _i32_bounds(preds, np2) \
        if pallas and _agg_i32_exact(padded_aggs, summed) else None
    k, m = len(datas), len(padded_aggs)
    ops, missed = _pool.fetch(
        list(datas) + list(valids)
        + [a[0] for a in padded_aggs] + [a[1] for a in padded_aggs])
    pd, pv = tuple(ops[:k]), tuple(ops[k:2 * k])
    ad, av = tuple(ops[2 * k:2 * k + m]), tuple(ops[2 * k + m:])
    out: Dict[str, Any] = {"sums": [], "mins": [], "maxs": [], "cnts": []}
    with jax.enable_x64(True):
        if bounds is not None:
            blk = np.asarray(_agg_pallas_core(pd, pv, bounds, ad, av,
                                              interpret=interpret))
            out["count"] = int(blk[4, 0])
            # an unread sum may have wrapped in int32: never report it
            per_col = [(int(blk[0, j]) if summed[j] else None,
                        int(blk[1, j]), int(blk[2, j]), int(blk[3, j]))
                       for j in range(m)]
            d2h = [blk]
        else:
            total, per = _agg_core(pd, pv, los, his, ad, av)
            out["count"] = int(total)
            per_col = [(s.item(), mn.item(), mx.item(), int(cnt))
                       for s, mn, mx, cnt in per]
            d2h = []
    _record_dispatch("fused_filter_aggregate", h2d=missed, d2h=d2h,
                     pallas=bounds is not None)
    for s, mn, mx, c in per_col:
        out["cnts"].append(c)
        out["sums"].append(s)
        out["mins"].append(mn if c else None)
        out["maxs"].append(mx if c else None)
    return out


# ---------------------------------------------------------------------------
# sorted intersection (columnar index access path)
# ---------------------------------------------------------------------------

@jax.jit
def _intersect_core(keys, cands):
    """Sorted merge via binary search: for each candidate, its insertion
    point in ``keys``; a hit scatters into the position bitmap."""
    _traced()
    n = keys.shape[0]
    pos = jnp.searchsorted(keys, cands)
    posc = jnp.clip(pos, 0, n - 1)
    hit = (pos < n) & (keys[posc] == cands)
    mask = jnp.zeros(n, dtype=jnp.int32)
    return mask.at[posc].add(hit.astype(jnp.int32)) > 0


def _sorted_merge_mask(keys: np.ndarray, cands: np.ndarray) -> np.ndarray:
    """Host (numpy) sorted merge: the one shared membership algorithm for
    the below-dispatch-floor branch and the object-dtype pk fallback in
    columnar/operators."""
    n = keys.shape[0]
    pos = np.searchsorted(keys, cands)
    posc = np.clip(pos, 0, n - 1)
    hit = (pos < n) & (keys[posc] == cands)
    mask = np.zeros(n, dtype=bool)
    mask[posc[hit]] = True
    return mask


def _pow2_pad(arr: np.ndarray) -> np.ndarray:
    """Pad a sorted array to the next power of two by duplicating its last
    element (stays sorted; duplicates never flip membership), bounding the
    jit retrace count to O(log n * log m) shape pairs.  Memoized by array
    identity in the device pool, so repeated probes over the same sorted
    keys reuse one padded view — which is itself a stable pool key."""
    return _pool.padded(arr, fill="edge")


@jax.jit
def _intersect_rank_core(keys, cands):
    """Membership plus its exclusive-cumsum rank fused into one dispatch:
    the merge path consumes the device mask on-device instead of round-
    tripping it to host between the bitmap and the rank pass."""
    _traced()
    n = keys.shape[0]
    pos = jnp.searchsorted(keys, cands)
    posc = jnp.clip(pos, 0, n - 1)
    hit = (pos < n) & (keys[posc] == cands)
    mask = jnp.zeros(n, dtype=jnp.int32)
    mem = mask.at[posc].add(hit.astype(jnp.int32)) > 0
    memi = mem.astype(jnp.int64)
    return mem, jnp.cumsum(memi) - memi


def _intersect_kernel(c_ref, k_ref, o_ref, *, m):
    """Membership of a [rb, 128] key tile in the SMEM-resident candidate
    set: the loop reads one candidate scalar per step and ORs a full
    vector compare — no gather, no host round-trip.  Edge-padded keys
    and candidates only repeat real values, so padding never flips a
    real row."""
    keys = k_ref[...]

    def body(j, acc):
        return jnp.maximum(acc, (keys == c_ref[j]).astype(jnp.int32))

    o_ref[...] = jax.lax.fori_loop(0, m, body, jnp.zeros_like(keys))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _intersect_pallas_core(keys, cands, interpret=False):
    _traced()
    rows, rb = _row_grid(keys.shape[0])
    out = pallas32(
        functools.partial(_intersect_kernel, m=cands.shape[0]),
        cands.astype(jnp.int32), keys.astype(jnp.int32).reshape(rows, LANES),
        grid=(rows // rb,),
        in_specs=[_smem(), _lane_block(rb)],
        out_specs=_lane_block(rb),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        interpret=interpret)
    return out.reshape(-1) != 0


def _intersect_on_pallas(keys: np.ndarray, cands: np.ndarray) -> bool:
    kp, cp = _pow2_pad(keys), _pow2_pad(cands)
    return kp.shape[0] % LANES == 0 and cp.shape[0] <= SMEM_CAP \
        and _pool.fits_int32(kp) and _pool.fits_int32(cp)


def _intersect_dispatch(keys: np.ndarray, cands: np.ndarray,
                        run_pallas: bool, interpret: bool) -> np.ndarray:
    n = keys.shape[0]
    kp, cp = _pow2_pad(keys), _pow2_pad(cands)
    ops, missed = _pool.fetch([kp, cp])
    with jax.enable_x64(True):
        if run_pallas:
            mask = _intersect_pallas_core(ops[0], ops[1], interpret=interpret)
        else:
            mask = _intersect_core(ops[0], ops[1])
        mask = np.asarray(mask)
    _record_dispatch("sorted_intersect_mask", h2d=missed, d2h=[mask],
                     pallas=run_pallas)
    return mask[:n]


def _intersect_rank_jnp(keys: np.ndarray, cands: np.ndarray
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """(membership, exclusive-cumsum rank) over ``keys`` in one dispatch
    (see ``_intersect_rank_core``)."""
    n = keys.shape[0]
    ops, missed = _pool.fetch([_pow2_pad(keys), _pow2_pad(cands)])
    with jax.enable_x64(True):
        mem_d, rank_d = _intersect_rank_core(ops[0], ops[1])
        mem, rank = np.asarray(mem_d), np.asarray(rank_d)
    _record_dispatch("sorted_intersect_mask", h2d=missed, d2h=[mem, rank])
    return mem[:n], rank[:n]


# ---------------------------------------------------------------------------
# dispatching wrappers
# ---------------------------------------------------------------------------

def range_mask(preds: Sequence[Pred], n: int,
               *, force_pallas: Optional[bool] = None,
               interpret: bool = False) -> np.ndarray:
    """Conjunctive range mask over K predicate columns -> bool [n].
    ``force_pallas`` overrides the backend check only: the Pallas kernel
    still runs only where it is exact."""
    if n == 0:
        return np.zeros(0, dtype=bool)
    if not preds:
        return np.ones(n, dtype=bool)
    pallas = use_pallas() if force_pallas is None else force_pallas
    return _mask_dispatch(preds, n, pallas, interpret)


def fused_filter_aggregate(preds: Sequence[Pred],
                           aggs: Sequence[Tuple[np.ndarray, np.ndarray]],
                           n: int, *, summed: Optional[Sequence[bool]] = None,
                           force_pallas: Optional[bool] = None,
                           interpret: bool = False) -> Dict[str, Any]:
    """Filter + reduce in one pass.

    Returns ``{"count", "sums", "mins", "maxs", "cnts"}`` where ``count``
    is the number of mask survivors and per-aggregate lists are aligned
    with ``aggs`` (``cnts`` = valid survivors per column; ``mins``/
    ``maxs`` are None when that is 0).  Values are python scalars in the
    column's own domain on either implementation.  ``summed`` flags the
    columns whose sum the caller reads (default: all); only those need
    an int32-exact sum for the Pallas kernel, and another column's sum
    may come back None.
    """
    if n == 0:
        return {"count": 0, "sums": [0] * len(aggs),
                "mins": [None] * len(aggs), "maxs": [None] * len(aggs),
                "cnts": [0] * len(aggs)}
    pallas = use_pallas() if force_pallas is None else force_pallas
    summed = [True] * len(aggs) if summed is None else list(summed)
    return _agg_dispatch(preds, aggs, n, pallas, interpret, summed)


def sorted_intersect_mask(keys: np.ndarray, cands: np.ndarray,
                          *, force_pallas: Optional[bool] = None,
                          interpret: bool = False) -> np.ndarray:
    """Position bitmap of a sorted candidate-PK array over a partition's
    sorted live-pk array: ``mask[i] == (keys[i] in cands)``.

    Empty inputs short-circuit (no zero-length kernel launch).  On TPU the
    Pallas membership kernel runs when both sides are int32-exact and the
    candidates fit SMEM (``SMEM_CAP``); otherwise the jitted x64
    searchsorted core keeps int64 pks exact, with the host sorted merge
    below the dispatch floor.
    """
    n, m = int(keys.shape[0]), int(cands.shape[0])
    if n == 0 or m == 0:
        return np.zeros(n, dtype=bool)
    pallas = use_pallas() if force_pallas is None else force_pallas
    run_pallas = pallas and _intersect_on_pallas(keys, cands)
    if not run_pallas and n + m <= 4096:
        # below the jax dispatch floor the host sorted merge wins outright
        return _sorted_merge_mask(keys, cands)
    return _intersect_dispatch(keys, cands, run_pallas, interpret)


# ---------------------------------------------------------------------------
# sorted k-way merge (columnar LSM merge path)
# ---------------------------------------------------------------------------

def _py_scalar_array(a: np.ndarray) -> np.ndarray:
    """Numeric array -> object array of python scalars, whose arbitrary-
    precision comparisons are exact across int/float domains (numpy
    cross-dtype scalar comparisons promote lossily)."""
    out = np.empty(a.shape[0], dtype=object)
    for j, v in enumerate(a.tolist()):
        out[j] = v
    return out


def _merge_take_object(arrays: Sequence[np.ndarray],
                       offs: Sequence[int]) -> Tuple[np.ndarray, np.ndarray]:
    """Host fallback for object-dtype keys (string / tuple pks): heapq
    merge of the per-component sorted runs; ties sort by component rank,
    so the first entry of each key group is the newest version."""
    def entries(i: int):
        a, off = arrays[i], offs[i]
        return ((a[j], i, off + j) for j in range(a.shape[0]))

    keys_l: List[Any] = []
    take_l: List[int] = []
    prev = sentinel = object()
    for key, _rank, pos in heapq.merge(
            *(entries(i) for i in range(len(arrays)) if arrays[i].shape[0])):
        if prev is sentinel or key != prev:
            keys_l.append(key)
            take_l.append(pos)
            prev = key
    out = np.empty(len(keys_l), dtype=object)
    for j, k in enumerate(keys_l):
        out[j] = k
    return out, np.asarray(take_l, dtype=np.int64)


def sorted_merge_take(key_arrays: Sequence[np.ndarray],
                      tombs: Optional[Sequence[np.ndarray]] = None,
                      *, drop_tombstones: bool = False,
                      force_pallas: Optional[bool] = None,
                      interpret: bool = False
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized k-way sorted-PK merge/dedup/tombstone-drop: the LSM
    merge kernel.

    ``key_arrays`` are per-component sorted, per-component-unique key
    arrays ordered newest -> oldest.  Returns ``(keys, take, tomb)``
    where ``keys`` is the sorted key union with the newest component
    winning each duplicate, ``take`` indexes the winning entries in the
    concatenation of ``key_arrays`` (in the given order) so every column
    of the merged output is a single gather, and ``tomb`` marks entries
    whose winner is a tombstone (``tombs`` aligned with ``key_arrays``).
    With ``drop_tombstones`` those entries are removed (the paper's
    merge-includes-oldest collapse).

    Numeric keys reuse the ``sorted_intersect_mask`` dispatch stack
    (Pallas membership kernel on TPU for int32-exact keys, the jitted
    pow2-padded x64 searchsorted oracle elsewhere, host merge below the
    dispatch floor): each component's membership bitmap over the sorted
    key union doubles as its position map via an exclusive cumsum —
    because every component key appears in the union, the number of
    union entries before position j that belong to component c *is*
    the rank of union[j] within component c.  Take-indices therefore
    come out of K vectorized membership passes with no per-row python
    loop.  Object keys fall back to a host heapq merge.
    """
    arrays = [np.asarray(a) for a in key_arrays]
    lens = [int(a.shape[0]) for a in arrays]
    offs = [0] * len(arrays)
    for i in range(1, len(arrays)):
        offs[i] = offs[i - 1] + lens[i - 1]
    nonempty = [i for i, l in enumerate(lens) if l]
    if not nonempty:
        z = np.zeros(0, dtype=np.int64)
        return z, z.copy(), np.zeros(0, dtype=bool)
    # mixed dtypes promote on concat: require a lossless round-trip
    # into the promoted dtype or fall back to the exact object merge
    numeric = all(arrays[i].dtype != object
                  and arrays[i].dtype.kind in "biuf" for i in nonempty) \
        and _promotes_lossless([arrays[i] for i in nonempty])
    if numeric:
        union = np.unique(np.concatenate([arrays[i] for i in nonempty]))
        pallas = use_pallas() if force_pallas is None else force_pallas
        take = np.full(union.shape[0], -1, dtype=np.int64)
        for i in nonempty:                  # newest first: first hit wins
            if pallas:
                mem = sorted_intersect_mask(union, arrays[i],
                                            force_pallas=force_pallas,
                                            interpret=interpret)
                pos = np.cumsum(mem) - mem  # exclusive cumsum == rank in c
            elif union.shape[0] + arrays[i].shape[0] <= 1 << 20:
                # merges see each (union, component) shape pair once, so
                # the jitted oracle's trace never amortizes off-TPU: the
                # host sorted merge gets a much higher floor than the
                # (repeatedly-hit) intersect kernel's
                mem = _sorted_merge_mask(union, arrays[i])
                pos = np.cumsum(mem) - mem
            else:
                # membership + rank fused on-device: the mask never
                # round-trips to host just to feed the cumsum
                mem, pos = _intersect_rank_jnp(union, arrays[i])
            sel = (take < 0) & mem
            take[sel] = offs[i] + pos[sel]
    else:
        arrays = [a if a.dtype == object else _py_scalar_array(a)
                  for a in arrays]
        union, take = _merge_take_object(arrays, offs)
    if tombs is None:
        return union, take, np.zeros(take.shape[0], dtype=bool)
    tomb_all = np.concatenate(
        [np.asarray(t, dtype=bool) for t, l in zip(tombs, lens) if l]) \
        if any(lens) else np.zeros(0, dtype=bool)
    tomb = tomb_all[take]
    if drop_tombstones and tomb.any():
        keep = ~tomb
        union, take, tomb = union[keep], take[keep], tomb[keep]
    return union, take, tomb
