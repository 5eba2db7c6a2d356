"""Mesh-parallel SPMD partition runtime: one ``shard_map`` dispatch for
all partitions instead of a python loop over them.

The paper's runtime is shared-nothing partitioned parallelism — Hyracks
operators run once per partition and Connectors move data between them.
Nine PRs in, our columnar engine still executed that model as a python
loop: per partition, one fused-chain / mask / aggregate dispatch plus a
``device_get``.  This module is the mesh analogue (ROADMAP item 2,
docs/ARCHITECTURE.md §"SPMD partition runtime"): per-partition
pow2-padded operands are stacked along a leading partition axis
(:class:`StackCache` keeps the stacked array identity-stable so the
device pool keeps it resident), a ``shard_map`` over the partition mesh
(axis ``"part"``) runs the same per-partition kernel body on every
shard via ``vmap``, and results come back in one transfer.  Hash
repartitioning lowers onto ``runtime/collectives.partition_by``
(``all_to_all``) and partial-aggregate merging onto column-wise
``psum``/``pmin``/``pmax`` collectives.

Activation is explicit and ambient: ``with use_partition_mesh(4):``
(or ``run_query(..., mesh=...)``) turns the SPMD paths on; with no
active mesh every consumer keeps the 1-device python-loop fallback, and
``tests/test_differential.py`` locks the two bit-for-bit.  The mesh spans
the devices this process holds: the chips of a TPU host, or for a CPU
rehearsal host devices from ``XLA_FLAGS=--xla_force_host_platform_
device_count=N`` set before Python starts (the CI mesh leg does this);
nothing here touches jax device state at import time.

Metrics (docs/METRICS.md §mesh):

  mesh.devices                  gauge: active partition-mesh size (0 when
                                no mesh is active)
  mesh.spmd_dispatches          counter: shard_map'ed SPMD dispatches
  mesh.spmd_partitions          counter: partitions covered by those
                                dispatches (loop dispatches would have
                                paid one call each)
  mesh.spmd_fallbacks           counter: SPMD-eligible calls that fell
                                back to the python loop (operand shape /
                                dtype disagreement across partitions)
  mesh.exchange_rows            counter: rows moved by the all_to_all
                                device exchange (connector repartition)
  mesh.shard<k>.h2d_bytes       counter: per-shard share of sharded
                                uploads (``fetch_sharded``)
  mesh.stack_hits               counter: ``StackCache`` lookups that found
                                the stacked array (a warm query)
  mesh.stack_misses             counter: lookups that built a new stack
  mesh.stack_bytes              counter: bytes of the stacks built
  mesh.tocc_dispatches          counter: stacked T-occurrence dispatches
                                (one a fuzzy index chain)
  mesh.tocc_hit_positions       counter: posting positions those
                                dispatches gathered (live hit lanes)
  mesh.tocc_count_cells         counter: int32 count cells they wrote
                                (one count row per stacked partition)
"""

from __future__ import annotations

import contextlib
import functools
import threading
import weakref
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from .. import obs
from ..kernels import device_pool as _pool
from ..obs import record_dispatch as _record_dispatch
from ..obs import record_retrace as _record_retrace
from .collectives import partition_by

__all__ = [
    "PART_AXIS", "partition_mesh", "use_partition_mesh", "active_mesh",
    "mesh_key", "dispatch_totals", "StackCache", "stack_cache",
    "fetch_sharded", "batched_range_masks", "batched_select_aggregate",
    "batched_t_occurrence", "exchange_batches", "psum_merge",
    "pmin_merge", "pmax_merge",
]

PART_AXIS = "part"

_DEVICES = obs.gauge("mesh.devices")
_DISPATCHES = obs.counter("mesh.spmd_dispatches")
_PARTITIONS = obs.counter("mesh.spmd_partitions")
_FALLBACKS = obs.counter("mesh.spmd_fallbacks")
_EXCH_ROWS = obs.counter("mesh.exchange_rows")
_STACK_HITS = obs.counter("mesh.stack_hits")
_STACK_MISSES = obs.counter("mesh.stack_misses")
_STACK_BYTES = obs.counter("mesh.stack_bytes")
_TOCC_DISPATCHES = obs.counter("mesh.tocc_dispatches")
_TOCC_HITS = obs.counter("mesh.tocc_hit_positions")
_TOCC_CELLS = obs.counter("mesh.tocc_count_cells")


# ---------------------------------------------------------------------------
# partition mesh context
# ---------------------------------------------------------------------------

_ACTIVE: List[Mesh] = []


def partition_mesh(devices: Optional[int] = None) -> Mesh:
    """1-d mesh over the first ``devices`` jax devices with the partition
    axis ``"part"``.  ``devices=None`` takes every visible device."""
    devs = jax.devices()
    n = len(devs) if devices is None else int(devices)
    if n < 1 or n > len(devs):
        raise ValueError(
            f"partition mesh wants {n} devices but {len(devs)} are visible")
    return Mesh(np.asarray(devs[:n]), (PART_AXIS,))


@contextlib.contextmanager
def use_partition_mesh(devices: Optional[int] = None,
                       mesh: Optional[Mesh] = None):
    """Activate a partition mesh for the executor's SPMD paths.  Inside
    the context, eligible per-partition loops (index chains, select
    masks, fused aggregates, hash exchanges) run as one ``shard_map``
    dispatch; outside it the python loop is the unconditional path."""
    m = mesh if mesh is not None else partition_mesh(devices)
    if PART_AXIS not in m.axis_names:
        raise ValueError(f"mesh {m} has no '{PART_AXIS}' axis")
    _ACTIVE.append(m)
    _DEVICES.set(int(m.devices.size))
    try:
        yield m
    finally:
        _ACTIVE.pop()
        _DEVICES.set(int(_ACTIVE[-1].devices.size) if _ACTIVE else 0)


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE[-1] if _ACTIVE else None


def mesh_key(mesh: Optional[Mesh] = None) -> Optional[Tuple]:
    """Hashable mesh signature for plan-cache keys: plan shapes compiled
    for the loop, a 2-device mesh, and a 4-device mesh are distinct
    entries (the jitted programs differ)."""
    m = mesh if mesh is not None else active_mesh()
    if m is None:
        return None
    return (PART_AXIS, int(m.devices.size),
            tuple(int(d.id) for d in m.devices.flat))


def mesh_size() -> int:
    m = active_mesh()
    return int(m.devices.size) if m is not None else 0


def dispatch_totals() -> Tuple[int, int]:
    """(spmd dispatches, partitions covered) — ExecStats diffs these per
    query, mirroring ``obs.kernel_totals``."""
    return (_DISPATCHES.value, _PARTITIONS.value)


def rows_for(n_real: int, mesh: Mesh) -> int:
    """Stack row count: partitions padded up to a multiple of the mesh
    size so shard_map's leading-axis split is even."""
    d = int(mesh.devices.size)
    return max(-(-n_real // d) * d, d)


_rows_for = rows_for


def note_fallback() -> None:
    """Count one SPMD-eligible call that fell back to the python loop
    (cross-partition operand drift)."""
    _FALLBACKS.inc()


def _note_spmd(mesh: Mesh, n_parts: int) -> None:
    _DISPATCHES.inc()
    _PARTITIONS.inc(n_parts)


# ---------------------------------------------------------------------------
# stacked-operand cache (identity-stable, so the device pool can keep the
# sharded upload resident across queries: warm mesh queries h2d == 0)
# ---------------------------------------------------------------------------

class StackCache:
    """Memoized stacking of per-partition pow2-padded operands along a
    leading partition axis.  Keyed by the identity of every input array
    plus the output geometry, guarded by weak references (any input
    dying drops the entry, and the stacked array's own death evicts its
    device copy through the pool's finalizer).  Entries are capped FIFO
    as a leak backstop — the working set is one stack per pooled operand
    per live LSM version, far under the cap."""

    def __init__(self, max_entries: int = 4096) -> None:
        self._lock = threading.Lock()
        self._entries: Dict[Tuple, Tuple[Tuple, np.ndarray, List]] = {}
        self._max = max_entries

    def stack(self, arrs: Sequence[Optional[np.ndarray]], rows: int,
              width: int, dtype: Any, fill: Any = 0) -> np.ndarray:
        """``[rows, width]`` array whose row ``i`` is ``arrs[i]`` (zero-
        padded to ``width``); ``None`` inputs and rows past ``len(arrs)``
        are ``fill``-rows (their lanes must be masked out by the
        caller's validity/liveness conjuncts)."""
        key = (tuple(0 if a is None else id(a) for a in arrs),
               rows, width, np.dtype(dtype).str, repr(fill))
        with self._lock:
            e = self._entries.get(key)
            if e is not None and all(
                    r() is a for r, a in zip(e[0], arrs) if r is not None):
                _STACK_HITS.inc()
                return e[1]
        out = np.full((rows, width), fill, dtype=dtype)
        for i, a in enumerate(arrs):
            if a is not None and a.shape[0]:
                out[i, :a.shape[0]] = a
        _STACK_MISSES.inc()
        _STACK_BYTES.inc(int(out.nbytes))
        refs = tuple(None if a is None else weakref.ref(a) for a in arrs)
        fins = []
        for a in arrs:
            if a is not None:
                fin = weakref.finalize(a, self._drop, key)
                fin.atexit = False
                fins.append(fin)
        with self._lock:
            if len(self._entries) >= self._max:
                oldest = next(iter(self._entries))
                self._drop_locked(oldest)
            self._entries[key] = (refs, out, fins)
        return out

    def _drop(self, key: Tuple) -> None:
        with self._lock:
            self._drop_locked(key)

    def _drop_locked(self, key: Tuple) -> None:
        e = self._entries.pop(key, None)
        if e is not None:
            for fin in e[2]:
                fin.detach()

    def clear(self) -> int:
        with self._lock:
            n = len(self._entries)
            for key in list(self._entries):
                self._drop_locked(key)
            return n

    def entry_count(self) -> int:
        return len(self._entries)


stack_cache = StackCache()


def fetch_sharded(arrs: Sequence[Any], mesh: Mesh
                  ) -> Tuple[List[Any], List[Any]]:
    """Pool-fetch stacked operands placed as partition-sharded device
    arrays (leading axis split over the mesh).  First touch uploads and
    is attributed per shard (``mesh.shard<k>.h2d_bytes``) on top of the
    usual kernel h2d accounting; later touches are pool hits, so a warm
    mesh query ships nothing."""
    placement = NamedSharding(mesh, PS(PART_AXIS))
    ops, missed = _pool.fetch(arrs, placement=placement)
    if missed:
        d = int(mesh.devices.size)
        for a in missed:
            per = int(a.nbytes) // d
            for k in range(d):
                obs.counter(f"mesh.shard{k}.h2d_bytes").inc(per)
    return ops, missed


# ---------------------------------------------------------------------------
# shard_map'ed kernel bodies (per-partition math vmapped over the local
# block; one jit trace per (mesh, structure, bucket) — counted exactly
# like the loop cores so retrace assertions keep holding)
# ---------------------------------------------------------------------------

def _traces() -> Dict[str, int]:
    from ..kernels.columnar_ops import _TRACES
    return _TRACES


@functools.lru_cache(maxsize=256)
def _chain_fn(mesh: Mesh, tiers_struct: Tuple[int, ...], n_preds: int,
              n_aggs: int, total_p2: int, live_p2: int):
    """jit(shard_map(vmap(chain math))) for one chain structure: the
    same fused Figure-6 math as ``plancache._chain_core``, run on every
    partition lane of the local shard (the program ``jit__chain_stack``
    on a device trace)."""
    from ..columnar.plancache import _chain_math
    tr = _traces()

    def _chain_stack(tiers, bounds, idx_pad, n_live, preds, aggds):
        tr["n"] += 1
        _record_retrace()

        def one(args):
            t, b, ix, nl, pr, ag = args
            return _chain_math(t, b, ix, nl, pr, ag, total_p2, live_p2)
        return jax.vmap(one)((tiers, bounds, idx_pad, n_live, preds, aggds))

    fn = shard_map(_chain_stack, mesh=mesh, in_specs=PS(PART_AXIS),
                   out_specs=PS(PART_AXIS))
    return jax.jit(fn)


def run_chain_stack(mesh: Mesh, tiers, bounds, idx_pad, n_live, preds,
                    aggds, total_p2: int, live_p2: int, n_parts: int):
    """Dispatch one stacked chain (plancache.run_all's device half).
    Stacked pooled operands go through :func:`fetch_sharded` (span
    ``chain.h2d``, as the loop's pool fetch); bound scalars stay dynamic
    [R] operands (excluded from h2d accounting by the kernel
    convention).  The core's call through its ``device_get`` is the
    ``chain.device`` span.  Returns host (n_cand, n_found, n_valid,
    mask, per_col) arrays with a leading partition-row axis."""
    tiers_struct = tuple(len(fp) for fp in tiers)
    flat: List[np.ndarray] = []
    for fp in tiers:
        flat.extend(fp)
    flat.append(idx_pad)
    for d, v, _lo, _hi in preds:
        flat.extend((d, v))
    for d, v in aggds:
        flat.extend((d, v))
    with obs.span("chain.h2d"):
        ops, missed = fetch_sharded(flat, mesh)
    it = iter(ops)
    dev_tiers = tuple(tuple(next(it) for _ in fp) for fp in tiers)
    dev_idx = next(it)
    dev_preds = tuple((next(it), next(it), lo, hi)
                      for _d, _v, lo, hi in preds)
    dev_aggs = tuple((next(it), next(it)) for _ in aggds)
    fn = _chain_fn(mesh, tiers_struct, len(preds), len(aggds),
                   total_p2, live_p2)
    with obs.span("chain.device"), jax.enable_x64(True):
        outs = fn(dev_tiers, bounds, dev_idx, n_live, dev_preds, dev_aggs)
        n_cand, n_found, n_valid, mask, per_col = jax.device_get(outs)
    mask_np = np.asarray(mask)
    _record_dispatch("spmd_index_chain", h2d=missed, d2h=[mask_np])
    _note_spmd(mesh, n_parts)
    return n_cand, n_found, n_valid, mask_np, per_col


@functools.lru_cache(maxsize=128)
def _tocc_fn(mesh: Mesh, tiers: Tuple[Tuple[int, int], ...], total_p2: int):
    """jit(shard_map(T-occurrence)) for one stacked tier structure (per
    tier slot its positions width and hits bucket).  A shard's partitions
    share one flat count, ``total_p2`` cells a partition and one sentinel:
    each partition's positions are raised by its row's base (``rowoff``,
    the tier offset plus ``row * total_p2``), and the shard's segments of
    a slot concatenate, so every slot is one 1-d gather and scatter-add
    with the loop kernel's math (``fuzzy_ops._tocc_tier_math``) and no
    batched scatter.  No collective: each chip counts its own
    partitions."""
    from ..kernels.fuzzy_ops import _tocc_tier_math
    tr = _traces()

    def _tocc_stack(pos, rowoff, starts, cum, nhits, thr):
        tr["n"] += 1
        _record_retrace()
        rows = rowoff.shape[0]
        cnt = jnp.zeros(rows * total_p2 + 1, dtype=jnp.int32)
        for t, (_w, hits_p2) in enumerate(tiers):
            flat = (pos[t] + rowoff[:, t:t + 1]).reshape(-1)
            cnt = _tocc_tier_math(cnt, flat, starts[0, t], cum[0, t],
                                  nhits[0, t], 0, hits_p2)
        return cnt[:-1].reshape(rows, total_p2) >= thr[:, None]

    fn = shard_map(_tocc_stack, mesh=mesh, in_specs=PS(PART_AXIS),
                   out_specs=PS(PART_AXIS))
    return jax.jit(fn)


def batched_t_occurrence(parts: Sequence[Any]
                         ) -> Optional[List[np.ndarray]]:
    """All partitions' ngram T-occurrence in one shard_map dispatch over
    the active mesh.  ``parts`` holds per partition either its finished
    candidate mask or its ``(live selection, tiers, storage concat
    length, T)`` operands (``storage/dataset._ngram_tocc_inputs``).
    Returns every partition's candidate mask, bit for bit the loop's
    ``ngram_t_occurrence(...)[live]``, or None (after a fallback count)
    where fewer than two partitions have hits or the positions' dtypes
    drift: the caller then runs the loop.  Tiers stack by slot (newest
    first), each slot padded to its widest positions array through
    :class:`StackCache`, so a warm query ships only segment bounds;
    partitions with fewer tiers, or no hits, scatter nothing from the
    empty slots.  Every partition that needs a count takes a row, hits or
    not, so which stacks a query uses depends on the data alone."""
    from ..columnar.batch import pow2_len
    mesh = active_mesh()
    if mesh is None:
        return None
    out: List[Optional[np.ndarray]] = [None] * len(parts)
    entries = []               # (partition, idx, tiers, total, T, hits)
    for i, x in enumerate(parts):
        if isinstance(x, np.ndarray):
            out[i] = x
            continue
        idx, tiers, total, thr = x
        hits = [int((en - st).sum()) for _o, _p, _pp, st, en in tiers]
        entries.append((i, idx, tiers, total, thr, hits))
    n_hit = sum(1 for e in entries if sum(e[5]))
    if not n_hit:
        for i, idx, *_ in entries:
            out[i] = np.zeros(len(idx), dtype=bool)
        return out
    dts = {str(t[2].dtype) for e in entries for t in e[2]}
    if n_hit < 2 or len(dts) != 1:
        _FALLBACKS.inc()
        return None
    with obs.span("fuzzy.tocc"):
        n_slots = max(len(e[2]) for e in entries)
        widths = [max(int(e[2][t][2].shape[0]) for e in entries
                      if t < len(e[2])) for t in range(n_slots)]
        gp = pow2_len(max(int(t[3].shape[0]) for e in entries
                          for t in e[2]) or 1)
        total_p2 = max(pow2_len(e[3]) for e in entries)
        rows = rows_for(len(entries), mesh)
        n_dev = int(mesh.devices.size)
        local = rows // n_dev
        dt = np.dtype(dts.pop())
        pos = [stack_cache.stack([e[2][t][2] if t < len(e[2]) else None
                                  for e in entries], rows, w, dt)
               for t, w in enumerate(widths)]
        # per shard and slot, its partitions' segments end to end: starts
        # index the shard's flattened positions, lengths 0 pad each row
        starts = np.zeros((n_dev, n_slots, local * gp), dtype=np.int64)
        lens = np.zeros((n_dev, n_slots, local * gp), dtype=np.int64)
        rowoff = np.zeros((rows, n_slots), dtype=np.int64)
        thr = np.zeros(rows, dtype=np.int32)
        for r, (_i, _idx, tiers, _total, t_thr, _hits) in enumerate(entries):
            d, lr = divmod(r, local)
            thr[r] = t_thr
            for t, (off, _p, _pp, st, en) in enumerate(tiers):
                lanes = slice(lr * gp, lr * gp + st.shape[0])
                starts[d, t, lanes] = st + lr * widths[t]
                lens[d, t, lanes] = en - st
                rowoff[r, t] = off + lr * total_p2
        cum = np.cumsum(lens, axis=2) - lens
        nhits = lens.sum(axis=2)
        slots = tuple((w, pow2_len(max(int(nhits[:, t].max()), 1)))
                      for t, w in enumerate(widths))
        ops, missed = fetch_sharded(pos, mesh)
        fn = _tocc_fn(mesh, slots, total_p2)
        with jax.enable_x64(True):
            mask = np.asarray(jax.device_get(
                fn(tuple(ops), rowoff, starts, cum, nhits, thr)))
        _record_dispatch("spmd_t_occurrence", h2d=missed, d2h=[mask])
        _note_spmd(mesh, len(entries))
        _TOCC_DISPATCHES.inc()
        _TOCC_HITS.inc(int(nhits.sum()))
        _TOCC_CELLS.inc(len(entries) * total_p2)
        for r, (i, idx, _tiers, total, _thr, _hits) in enumerate(entries):
            out[i] = mask[r, :total][idx]
    return out


@functools.lru_cache(maxsize=128)
def _mask_fn(mesh: Mesh, n_preds: int, live_p2: int):
    """Stacked twin of ``columnar_ops._mask_core`` (same conjunct order,
    so masks are bit-identical to the loop kernel's)."""
    tr = _traces()

    def body(datas, valids, los, his):
        tr["n"] += 1
        _record_retrace()

        def one(args):
            ds, vs, ls, hs = args
            m = None
            for x, v, lo, hi in zip(ds, vs, ls, hs):
                mm = v & (x >= lo) & (x <= hi)
                m = mm if m is None else (m & mm)
            return m
        return jax.vmap(one)((datas, valids, los, his))

    fn = shard_map(body, mesh=mesh, in_specs=PS(PART_AXIS),
                   out_specs=PS(PART_AXIS))
    return jax.jit(fn)


@functools.lru_cache(maxsize=128)
def _agg_fn(mesh: Mesh, n_preds: int, n_aggs: int, live_p2: int):
    """Stacked twin of ``columnar_ops._agg_core`` plus the mask (the
    caller's non-kernelable aggregate columns reduce host-side over the
    mask-filtered batch, exactly like ``operators.aggregate_batch``)."""
    from ..kernels.columnar_ops import _ident
    tr = _traces()

    def body(datas, valids, los, his, adatas, avalids):
        tr["n"] += 1
        _record_retrace()

        def one(args):
            ds, vs, ls, hs, ads, avs = args
            mask = None
            for x, v, lo, hi in zip(ds, vs, ls, hs):
                mm = v & (x >= lo) & (x <= hi)
                mask = mm if mask is None else (mask & mm)
            total = jnp.sum(mask)
            per_col = []
            for x, v in zip(ads, avs):
                ok = mask & v
                cnt = jnp.sum(ok)
                s = jnp.sum(jnp.where(ok, x, jnp.asarray(0, x.dtype)))
                mn = jnp.min(jnp.where(ok, x, _ident(x.dtype, True)))
                mx = jnp.max(jnp.where(ok, x, _ident(x.dtype, False)))
                per_col.append((s, mn, mx, cnt))
            return total, mask, tuple(per_col)
        return jax.vmap(one)((datas, valids, los, his, adatas, avalids))

    fn = shard_map(body, mesh=mesh, in_specs=PS(PART_AXIS),
                   out_specs=PS(PART_AXIS))
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# batched select masks (STREAM_SELECT over all partitions at once)
# ---------------------------------------------------------------------------

def _stack_preds(entries, ranges_len: int, mesh: Mesh
                 ) -> Optional[Tuple]:
    """Stack per-partition kernel predicates into [R, live_p2] operands
    plus [R] bound vectors.  ``entries`` is [(partition, preds, ...)];
    returns None when dtypes disagree across partitions (rare open-type
    drift — the python loop handles it)."""
    from ..kernels.columnar_ops import _prep_bounds
    preds0 = entries[0][1]
    dts = tuple(str(p[0].dtype) for p in preds0)
    for e in entries[1:]:
        if tuple(str(p[0].dtype) for p in e[1]) != dts:
            return None
    live_p2 = max(int(p[0].shape[0]) for e in entries for p in e[1])
    rows = _rows_for(len(entries), mesh)
    datas, valids, los, his = [], [], [], []
    for j in range(ranges_len):
        dt0 = entries[0][1][j][0].dtype
        d_list = [e[1][j][0] for e in entries]
        v_list = [e[1][j][1] for e in entries]
        datas.append(stack_cache.stack(d_list, rows, live_p2, dt0))
        valids.append(stack_cache.stack(v_list, rows, live_p2, np.bool_,
                                        fill=False))
        lo_a = np.zeros(rows, dtype=dt0)
        hi_a = np.zeros(rows, dtype=dt0)
        for r, e in enumerate(entries):
            _d, _v, lo, hi = e[1][j]
            blo, bhi = _prep_bounds(_d, lo, hi)
            lo_a[r], hi_a[r] = blo, bhi
        los.append(lo_a)
        his.append(hi_a)
    return datas, valids, los, his, live_p2, rows


def batched_range_masks(batches: Sequence[Any],
                        ranges: Dict[str, Tuple[Any, Any]]
                        ) -> Optional[List[Optional[np.ndarray]]]:
    """All partitions' ``K.range_mask`` in one shard_map dispatch.
    Returns per-partition boolean masks (None entries: partition needs
    the host path — empty batch or absent column), or None when the
    whole select should stay on the python loop."""
    mesh = active_mesh()
    if mesh is None or not ranges:
        return None
    from ..columnar import operators as O
    entries = []            # (partition index, preds)
    for i, b in enumerate(batches):
        if len(b) == 0:
            continue
        made = O.make_range_preds(b, ranges)
        if made is None:
            _FALLBACKS.inc()
            return None     # not vectorizable anywhere: row-engine path
        if made is O.EMPTY:
            continue        # host short-circuit (empty result)
        entries.append((i, made))
    if len(entries) < 2:
        return None         # nothing to gain from a collective dispatch
    stacked = _stack_preds(entries, len(ranges), mesh)
    if stacked is None:
        _FALLBACKS.inc()
        return None
    datas, valids, los, his, live_p2, rows = stacked
    k = len(datas)
    flat = list(datas) + list(valids)
    ops, missed = fetch_sharded(flat, mesh)
    fn = _mask_fn(mesh, k, live_p2)
    with jax.enable_x64(True):
        out = np.asarray(jax.device_get(
            fn(tuple(ops[:k]), tuple(ops[k:]), tuple(los), tuple(his))))
    _record_dispatch("spmd_range_mask", h2d=missed, d2h=[out])
    _note_spmd(mesh, len(entries))
    result: List[Optional[np.ndarray]] = [None] * len(batches)
    for r, (i, _preds) in enumerate(entries):
        result[i] = out[r, :len(batches[i])]
    return result


# ---------------------------------------------------------------------------
# batched fused select+aggregate (LOCAL_AGG over an exact-range select)
# ---------------------------------------------------------------------------

def batched_select_aggregate(batches: Sequence[Any],
                             ranges: Dict[str, Tuple[Any, Any]],
                             aggs: Dict[str, Tuple[str, str]]
                             ) -> Optional[List[Optional[Tuple]]]:
    """All partitions' ``fused_select_aggregate`` in one shard_map
    dispatch.  Returns per-partition ``(row, survivors)`` results (None
    entries fall back to the per-partition host kernel), or None when
    partitions disagree structurally and the loop should run."""
    mesh = active_mesh()
    if mesh is None or not ranges:
        return None
    from ..columnar import operators as O
    entries = []   # (i, preds, n, arrays, meta, batch)
    for i, b in enumerate(batches):
        n = len(b)
        if n == 0:
            continue
        made = O.make_range_preds(b, ranges)
        if made is None:
            _FALLBACKS.inc()
            return None
        if made is O.EMPTY:
            continue
        arrays, meta = O._kernel_agg_cols(b, aggs)
        entries.append((i, made, n, arrays, meta, b))
    if len(entries) < 2:
        return None
    sig0 = tuple((m[0], m[1], m[2]) for m in entries[0][4])
    adts = tuple(str(a[0].dtype) for a in entries[0][3])
    for e in entries[1:]:
        if tuple((m[0], m[1], m[2]) for m in e[4]) != sig0 \
                or tuple(str(a[0].dtype) for a in e[3]) != adts:
            _FALLBACKS.inc()
            return None
    stacked = _stack_preds(entries, len(ranges), mesh)
    if stacked is None:
        _FALLBACKS.inc()
        return None
    datas, valids, los, his, live_p2, rows = stacked
    live_p2 = max([live_p2] + [int(a[0].shape[0])
                               for e in entries for a in e[3]])
    if live_p2 != stacked[4]:
        # aggregate columns sit in a larger bucket: restack predicates
        stacked = None
    if stacked is None:
        return None      # pred/agg bucket split: loop path (rare)
    m = len(adts)
    adatas, avalids = [], []
    for j in range(m):
        dt0 = entries[0][3][j][0].dtype
        adatas.append(stack_cache.stack([e[3][j][0] for e in entries],
                                        rows, live_p2, dt0))
        avalids.append(stack_cache.stack([e[3][j][1] for e in entries],
                                         rows, live_p2, np.bool_,
                                         fill=False))
    k = len(datas)
    flat = list(datas) + list(valids) + adatas + avalids
    ops, missed = fetch_sharded(flat, mesh)
    fn = _agg_fn(mesh, k, m, live_p2)
    with jax.enable_x64(True):
        outs = fn(tuple(ops[:k]), tuple(ops[k:2 * k]),
                  tuple(los), tuple(his),
                  tuple(ops[2 * k:2 * k + m]), tuple(ops[2 * k + m:]))
        total_a, mask_a, per_col_a = jax.device_get(outs)
    mask_np = np.asarray(mask_a)
    _record_dispatch("spmd_filter_aggregate", h2d=missed, d2h=[mask_np])
    _note_spmd(mesh, len(entries))
    result: List[Optional[Tuple]] = [None] * len(batches)
    for r, (i, _preds, n, _arrays, meta, b) in enumerate(entries):
        res = {"count": int(total_a[r]), "sums": [], "mins": [],
               "maxs": [], "cnts": []}
        for s, mn, mx, cnt in per_col_a:
            c = int(cnt[r])
            res["cnts"].append(c)
            res["sums"].append(s[r].item())
            res["mins"].append(mn[r].item() if c else None)
            res["maxs"].append(mx[r].item() if c else None)
        row_mask = mask_np[r]
        result[i] = O._finish_aggregate(
            aggs, meta, res, True,
            lambda bb=b, mm=row_mask, nn=n: bb.filter(mm[:nn]))
    return result


# ---------------------------------------------------------------------------
# hash repartitioning on the mesh (MToNPartitioningConnector -> all_to_all)
# ---------------------------------------------------------------------------

_EXCHANGE_KINDS = ("i64", "f64", "bool", "dt", "date")


@functools.lru_cache(maxsize=64)
def _exchange_fn(mesh: Mesh, n_arrays: int, cap: int):
    tr = _traces()

    def body(*arrs):
        tr["n"] += 1
        _record_retrace()
        outs = []
        for x in arrs:          # local [1, p, cap]
            y = partition_by(x[0], PART_AXIS, split_dim=0, concat_dim=0)
            outs.append(y[None])
        return tuple(outs)

    fn = shard_map(body, mesh=mesh, in_specs=PS(PART_AXIS),
                   out_specs=PS(PART_AXIS))
    return jax.jit(fn)


def exchange_batches(cparts: Sequence[Any], keys: Sequence[str], p: int
                     ) -> Optional[Tuple[List[Any], int]]:
    """Hash-repartition ColumnBatches across the mesh with one tiled
    ``all_to_all`` per column plane (MToNPartitioningConnector lowered
    onto the ICI collective, paper §4.1).  Placement and row order are
    bit-identical to the host bucketing path (same ``partition_ids``
    hash, source-major row order).  Returns (batches, rows moved), or
    None when the exchange must stay host-side: mesh size != partition
    count, schema drift across partitions, or non-numeric (string/obj)
    columns whose dictionary codes are partition-local."""
    mesh = active_mesh()
    if mesh is None or int(mesh.devices.size) != p or p < 2:
        return None
    from ..columnar import operators as O
    from ..columnar.batch import Column, ColumnBatch, pow2_len
    schema: Optional[Tuple] = None
    for b in cparts:
        if not len(b):
            continue
        sig = tuple(sorted((nm, c.kind) for nm, c in b.columns.items()))
        if any(kd not in _EXCHANGE_KINDS for _nm, kd in sig):
            return None
        if schema is None:
            schema = sig
        elif sig != schema:
            _FALLBACKS.inc()
            return None
    if schema is None:
        return None                      # all partitions empty: host path
    counts = np.zeros((p, p), dtype=np.int64)
    orders: List[Optional[np.ndarray]] = []
    moved = 0
    for i, b in enumerate(cparts):
        if not len(b):
            orders.append(None)
            continue
        pid = O.partition_ids(b, keys, p)
        moved += int((pid != i).sum())
        orders.append(np.argsort(pid, kind="stable"))
        counts[i] = np.bincount(pid, minlength=p)
    cap = pow2_len(int(counts.max()))
    if cap == 0:
        return None
    names = [nm for nm, _kd in schema]
    kinds = dict(schema)
    ref = next(b for b in cparts if len(b))
    send: List[np.ndarray] = []
    for nm in names:
        dt0 = ref.columns[nm].data.dtype
        data_s = np.zeros((p, p, cap), dtype=dt0)
        valid_s = np.zeros((p, p, cap), dtype=bool)
        for i, b in enumerate(cparts):
            if not len(b):
                continue
            col = b.columns[nm]
            d_srt = col.data[orders[i]]
            v_srt = col.valid[orders[i]]
            offs = np.concatenate([[0], np.cumsum(counts[i])])
            for j in range(p):
                a, z = int(offs[j]), int(offs[j + 1])
                data_s[i, j, :z - a] = d_srt[a:z]
                valid_s[i, j, :z - a] = v_srt[a:z]
        send.extend((data_s, valid_s))
    fn = _exchange_fn(mesh, len(send), cap)
    with jax.enable_x64(True):
        recv = [np.asarray(a) for a in jax.device_get(fn(*send))]
    _record_dispatch("spmd_exchange", h2d=send, d2h=recv)
    _note_spmd(mesh, p)
    _EXCH_ROWS.inc(moved)
    out: List[Any] = []
    for j in range(p):
        n_j = int(counts[:, j].sum())
        if n_j == 0:
            out.append(ColumnBatch({}, 0))
            continue
        cols: Dict[str, Column] = {}
        for c_idx, nm in enumerate(names):
            recv_d = recv[2 * c_idx]
            recv_v = recv[2 * c_idx + 1]
            data = np.concatenate(
                [recv_d[j, i, :counts[i, j]] for i in range(p)])
            valid = np.concatenate(
                [recv_v[j, i, :counts[i, j]] for i in range(p)])
            cols[nm] = Column(kinds[nm], data, valid, None)
        out.append(ColumnBatch(cols, n_j))
    return out, moved


# ---------------------------------------------------------------------------
# column-wise collective merge of partial aggregates
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _merge_fn(mesh: Mesh, op: str):
    tr = _traces()
    local = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[op]
    glob = {"sum": jax.lax.psum, "min": jax.lax.pmin, "max": jax.lax.pmax}[op]

    def body(x):                         # local [R/D, M]
        tr["n"] += 1
        _record_retrace()
        return glob(local(x, axis=0), PART_AXIS)

    fn = shard_map(body, mesh=mesh, in_specs=PS(PART_AXIS),
                   out_specs=PS())
    return jax.jit(fn)


def _collective_merge(parts: np.ndarray, op: str,
                      mesh: Optional[Mesh]) -> np.ndarray:
    m = mesh if mesh is not None else active_mesh()
    if m is None:
        raise RuntimeError("no active partition mesh")
    parts = np.asarray(parts)
    if parts.ndim == 1:
        parts = parts[:, None]
        squeeze = True
    else:
        squeeze = False
    rows = _rows_for(parts.shape[0], m)
    if rows != parts.shape[0]:
        if op == "sum":
            fill = np.zeros((rows - parts.shape[0], parts.shape[1]),
                            dtype=parts.dtype)
        else:
            if np.issubdtype(parts.dtype, np.integer):
                info = np.iinfo(parts.dtype)
                ident = info.max if op == "min" else info.min
            else:
                ident = np.inf if op == "min" else -np.inf
            fill = np.full((rows - parts.shape[0], parts.shape[1]),
                           ident, dtype=parts.dtype)
        parts = np.concatenate([parts, fill])
    fn = _merge_fn(m, op)
    with jax.enable_x64(True):
        out = np.asarray(jax.device_get(fn(parts)))
    _record_dispatch(f"spmd_merge_{op}", d2h=[out])
    _note_spmd(m, parts.shape[0])
    return out[:, 0] if squeeze and out.ndim == 2 else out


def psum_merge(parts: np.ndarray, mesh: Optional[Mesh] = None) -> np.ndarray:
    """Column-wise psum of per-partition partial aggregates [P, M] -> [M]
    (GLOBAL_AGG's sum/count merge as one collective; exact for the
    integer-domain aggregates the executor keys correctness on)."""
    return _collective_merge(parts, "sum", mesh)


def pmin_merge(parts: np.ndarray, mesh: Optional[Mesh] = None) -> np.ndarray:
    return _collective_merge(parts, "min", mesh)


def pmax_merge(parts: np.ndarray, mesh: Optional[Mesh] = None) -> np.ndarray:
    return _collective_merge(parts, "max", mesh)
