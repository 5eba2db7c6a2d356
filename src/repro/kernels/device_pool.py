"""Device-resident buffer pool for immutable columnar operands.

LSM components never mutate — they appear at flush/merge and retire when
replaced — so their pow2-padded column arrays and CSR postings arrays
are safe to keep device-side across queries.  The pool maps a *host*
array (by identity) to its device copy: the first touch uploads (the
caller records the host bytes as ``h2d``), every later touch returns the
resident copy for free.  Because kernel wrappers only account
``np.ndarray`` operands as transfer bytes (``obs.record_dispatch``), a
fully-resident dispatch naturally reports ``h2d_bytes == 0``.

Keying is by ``(id(arr), placement)`` guarded with a weak reference: the
pow2-padded views are already shape- and identity-stable per LSM version
(``Column.padded``, ``FieldPostings.padded_positions``, the partition
scan cache), so one component column is one pool entry for the
component's whole lifetime.  ``placement`` is None for the default
single-device copy or a ``NamedSharding`` for mesh-sharded uploads
(``runtime/spmd.fetch_sharded`` — stacked partition operands split over
the partition axis, attributed per shard via ``mesh.shard<k>.h2d_bytes``).
An array lives under at most one placement at a time: uploading it with
a *different* placement evicts the other copies first (reshard eviction,
``buffer_pool.reshard_evictions``), so switching between the loop and a
mesh — or between meshes — never double-holds device memory.  Eviction
is otherwise driven from two sides:

  * ``core/lsm.py`` calls :func:`release_component` at the two places a
    component's ``retired`` flag flips — immediate retirement at merge,
    or deferred retirement once the last snapshot pin drops — the same
    discipline the host arrays already follow;
  * a ``weakref.finalize`` per entry evicts when the host array is
    garbage-collected anyway (dropped scan-cache versions, pre-crash
    memtable postings after ``crash_and_recover``, throwaway operands),
    so the pool cannot leak buffers for arrays nothing references.

Metrics (see the registry docstring in ``obs/__init__``):
``buffer_pool.hits`` / ``buffer_pool.misses`` / ``buffer_pool.evictions``
counters and the ``buffer_pool.resident_bytes`` gauge.

The pool also memoizes *host-side* pow2 padding (:meth:`DevicePool.padded`)
so repeated probes over the same sorted-key arrays reuse one padded view
— which is what makes the padded array a stable pool key in turn.
"""

from __future__ import annotations

import threading
import weakref
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..columnar.batch import pow2_len as _pow2_len

__all__ = ["DevicePool", "pool", "fetch", "padded", "memo", "fits_int32",
           "abs_sum",
           "release_component", "clear", "stats"]

_HITS = obs.counter("buffer_pool.hits")
_MISSES = obs.counter("buffer_pool.misses")
_EVICTIONS = obs.counter("buffer_pool.evictions")
_RESHARDS = obs.counter("buffer_pool.reshard_evictions")
_RESIDENT = obs.gauge("buffer_pool.resident_bytes")
_I32 = np.iinfo(np.int32)


def _poolable(a: Any) -> bool:
    return isinstance(a, np.ndarray) and a.dtype != object


class DevicePool:
    """Identity-keyed host->device buffer cache (thread-safe)."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        # (id(host), placement) -> (weakref(host), device, nbytes, finalizer)
        self._entries: Dict[Tuple[int, Any],
                            Tuple[Any, Any, int, Any]] = {}
        # id(host) -> placements currently resident for that id
        self._by_id: Dict[int, set] = {}
        # (id(host), tag) -> (weakref(host), derived value, finalizer):
        # padded views (tag "edge"/"zero") and per-array facts
        self._pads: Dict[Tuple[int, str], Tuple[Any, Any, Any]] = {}
        self._resident = 0

    # -- residency ----------------------------------------------------------

    def get(self, arr: np.ndarray, placement: Any = None
            ) -> Tuple[Any, bool]:
        """Device copy of ``arr`` under ``placement`` (None: default
        device; a ``NamedSharding``: mesh-sharded) plus whether it was
        already resident.  Uploads happen under x64 so
        int64/float64 operands keep their width (matching the jnp-oracle
        kernel convention).  Uploading under a new placement evicts the
        array's copies under any other placement first (reshard
        eviction) — an operand is resident one way at a time."""
        key = (id(arr), placement)
        with self._lock:
            e = self._entries.get(key)
            if e is not None and e[0]() is arr:
                _HITS.inc()
                return e[1], True
        with jax.enable_x64(True):
            if placement is None:
                dev = jnp.asarray(arr)
            else:
                dev = jax.device_put(arr, placement)
        nb = int(arr.nbytes)
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                if e[0]() is arr:          # lost an upload race: keep first
                    _HITS.inc()
                    return e[1], True
                self._drop(key, e)         # stale entry under a reused id
            for other in list(self._by_id.get(id(arr), ())):
                if other != placement:     # reshard: drop the other copies
                    oe = self._entries.get((id(arr), other))
                    if oe is not None:
                        self._drop((id(arr), other), oe)
                        _RESHARDS.inc()
            fin = weakref.finalize(arr, self._on_dead, key)
            fin.atexit = False
            self._entries[key] = (weakref.ref(arr), dev, nb, fin)
            self._by_id.setdefault(id(arr), set()).add(placement)
            self._resident += nb
            _RESIDENT.set(self._resident)
        _MISSES.inc()
        return dev, False

    def fetch(self, arrs: Sequence[Any], placement: Any = None
              ) -> Tuple[List[Any], List[Any]]:
        """Map operands to device copies.  Returns ``(operands, missed)``
        where ``missed`` lists the host arrays uploaded by this call —
        exactly what the caller should report as ``h2d`` (pool hits ship
        nothing; non-poolable operands pass through untouched and keep
        their existing accounting)."""
        out: List[Any] = []
        missed: List[Any] = []
        for a in arrs:
            if _poolable(a):
                dev, hit = self.get(a, placement)
                out.append(dev)
                if not hit:
                    missed.append(a)
            else:
                out.append(a)
        return out, missed

    # -- host-side pad memo -------------------------------------------------

    def padded(self, arr: np.ndarray, fill: str = "edge") -> np.ndarray:
        """Pow2-padded host view of a 1-d array, memoized by identity so
        the padded array (the actual pool key) is stable across calls.
        ``fill="edge"`` repeats the last element (keeps sorted arrays
        sorted); ``fill="zero"`` pads with zeros (safe for index arrays
        whose padding lanes are masked out)."""
        n = int(arr.shape[0])
        np2 = _pow2_len(n)
        if np2 == n and n > 0:
            return arr

        def build(a: np.ndarray) -> np.ndarray:
            if n == 0:
                return np.zeros(max(np2, 1), dtype=a.dtype)
            tail = a[-1] if fill == "edge" else 0
            return np.concatenate([a, np.full(np2 - n, tail, dtype=a.dtype)])
        return self.memo(arr, fill, build)

    def fits_int32(self, arr: np.ndarray) -> bool:
        """Whether every element of an integer (or bool) array lies in
        the int32 range — the domain of the Pallas kernels, which compare
        and reduce in 32-bit lanes.  Memoized by identity: component
        columns are immutable, so one host pass per array suffices."""
        def build(a: np.ndarray) -> bool:
            if a.dtype.kind not in "biu":
                return False
            if a.size == 0 or a.dtype.kind == "b":
                return True
            return bool(int(a.min()) >= _I32.min and int(a.max()) <= _I32.max)
        return self.memo(arr, "i32", build)

    def abs_sum(self, arr: np.ndarray) -> int:
        """Sum of absolute values of an integer array (exact python int),
        memoized by identity: bounds every partial sum a kernel can form
        over any subset of the array."""
        return self.memo(arr, "abssum", lambda a: int(
            np.abs(a.astype(np.int64)).sum(dtype=np.int64)))

    def memo(self, arr: Any, tag: str, build: Any) -> Any:
        """Value derived from ``arr`` (any weak-referenceable immutable
        owner) by ``build``, kept while ``arr`` lives: identity-keyed and
        weakref-guarded like the residency map, so a derived host array
        is itself a stable pool key."""
        key = (id(arr), tag)
        with self._lock:
            m = self._pads.get(key)
            if m is not None and m[0]() is arr:
                return m[1]
        val = build(arr)
        with self._lock:
            m = self._pads.get(key)
            if m is not None and m[0]() is arr:
                return m[1]
            fin = weakref.finalize(arr, self._on_dead_pad, key)
            fin.atexit = False
            self._pads[key] = (weakref.ref(arr), val, fin)
        return val

    # -- eviction -----------------------------------------------------------

    def release(self, arr: Any) -> None:
        """Explicitly evict ``arr``'s device copy and any padded views
        derived from it (their own device copies included)."""
        if not isinstance(arr, np.ndarray):
            return
        with self._lock:
            for fill in ("edge", "zero"):
                m = self._pads.pop((id(arr), fill), None)
                if m is not None:
                    m[2].detach()
                    self._release_exact(m[1])
            self._release_exact(arr)

    def release_component(self, comp: Any) -> None:
        """Eviction hook for LSM component retirement: free every device
        buffer backed by the component's arrays (keys, tombstones, batch
        columns + their cached padded/int64 views, secondary and ngram
        postings + their cached padded positions)."""
        arrs: List[Any] = [getattr(comp, "keys", None),
                           getattr(comp, "tomb", None)]
        batch = getattr(comp, "batch", None)
        if batch is not None:
            for col in batch.columns.values():
                arrs.extend((col.data, col.valid))
                for cached in (getattr(col, "_padded", None),
                               getattr(col, "_padded_i64", None)):
                    if cached is not None:
                        arrs.extend(cached)
        posts = list(getattr(comp, "sec_postings", {}).values()) \
            + list(getattr(comp, "gram_postings", {}).values())
        for p in posts:
            if p is None:
                continue
            arrs.extend((getattr(p, "keys", None), p.offsets, p.positions,
                         p.has_value, getattr(p, "_padded", None)))
        with self._lock:
            for a in arrs:
                if a is not None:
                    self.release(a)

    def clear(self) -> int:
        """Evict everything (bench cold-start helper).  Returns the
        number of entries dropped."""
        with self._lock:
            n = len(self._entries)
            for key, e in list(self._entries.items()):
                self._drop(key, e)
            for m in self._pads.values():
                m[2].detach()
            self._pads.clear()
            return n

    # -- introspection ------------------------------------------------------

    def resident_bytes(self) -> int:
        return self._resident

    def entry_count(self) -> int:
        return len(self._entries)

    def placed_device_counts(self) -> List[int]:
        """Number of distinct devices holding each resident copy uploaded
        under an explicit placement (mesh-sharded stacks): a partition-
        sharded operand on an N-device mesh spans N devices."""
        with self._lock:
            return [len(e[1].devices()) for key, e in self._entries.items()
                    if key[1] is not None]

    def stats(self) -> Dict[str, int]:
        return {"entries": len(self._entries),
                "resident_bytes": self._resident,
                "hits": _HITS.value, "misses": _MISSES.value,
                "evictions": _EVICTIONS.value}

    # -- internals ----------------------------------------------------------

    def _release_exact(self, arr: np.ndarray) -> None:
        for placement in list(self._by_id.get(id(arr), ())):
            key = (id(arr), placement)
            e = self._entries.get(key)
            if e is not None and (e[0]() is arr or e[0]() is None):
                self._drop(key, e)

    def _drop(self, key: Tuple[int, Any],
              e: Tuple[Any, Any, int, Any]) -> None:
        if self._entries.get(key) is not e:
            return
        del self._entries[key]
        placements = self._by_id.get(key[0])
        if placements is not None:
            placements.discard(key[1])
            if not placements:
                del self._by_id[key[0]]
        e[3].detach()
        self._resident -= e[2]
        _RESIDENT.set(self._resident)
        _EVICTIONS.inc()

    def _on_dead(self, key: Tuple[int, Any]) -> None:
        # host array was garbage-collected: drop the device copy (RLock:
        # safe even if the collection triggered under our own lock)
        with self._lock:
            e = self._entries.get(key)
            if e is not None and e[0]() is None:
                self._drop(key, e)

    def _on_dead_pad(self, key: Tuple[int, str]) -> None:
        with self._lock:
            m = self._pads.pop(key, None)
            # the padded host array dies with the memo entry; its own
            # finalizer then evicts its device copy
            if m is not None:
                m[2].detach()


pool = DevicePool()


def fetch(arrs: Sequence[Any], placement: Any = None
          ) -> Tuple[List[Any], List[Any]]:
    return pool.fetch(arrs, placement)


def padded(arr: np.ndarray, fill: str = "edge") -> np.ndarray:
    return pool.padded(arr, fill)


def memo(arr: Any, tag: str, build: Any) -> Any:
    return pool.memo(arr, tag, build)


def fits_int32(arr: np.ndarray) -> bool:
    return pool.fits_int32(arr)


def abs_sum(arr: np.ndarray) -> int:
    return pool.abs_sum(arr)


def release_component(comp: Any) -> None:
    pool.release_component(comp)


def clear() -> int:
    return pool.clear()


def stats() -> Dict[str, int]:
    return pool.stats()
