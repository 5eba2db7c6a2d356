"""Shared benchmark timing — one best-of-N implementation for every
benchmark module.

Every bench used to carry its own copy of the ``perf_counter`` best-of-N
loop (and ``fuzzy_bench`` timed its wall clock with non-monotonic
``time.time()``).  This module is the single source of truth:

  timed(fn)      — best-of-N wall time for a callable.
  stopwatch()    — context manager for one-shot sections (ingest loops,
                   end-to-end pipelines); monotonic by construction.

All times are ``time.perf_counter()`` seconds.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Optional, Tuple

__all__ = ["timed", "stopwatch", "Stopwatch"]


def timed(fn: Callable[[], Any], repeat: int = 3, warmup: int = 0,
          block: Optional[Callable[[Any], Any]] = None,
          ) -> Tuple[Any, float]:
    """Run ``fn`` ``warmup + repeat`` times; return (last output,
    best seconds over the timed repetitions).

    ``block`` (e.g. ``jax.block_until_ready``) is applied to the output
    inside the timed region so async dispatch is charged to the bench.
    """
    out = None
    for _ in range(warmup):
        out = fn()
        if block is not None:
            block(out)
    best = float("inf")
    for _ in range(max(1, repeat)):
        t0 = time.perf_counter()
        out = fn()
        if block is not None:
            block(out)
        best = min(best, time.perf_counter() - t0)
    return out, best


class Stopwatch:
    """``with stopwatch() as sw: ...`` then read ``sw.seconds``."""

    __slots__ = ("_t0", "seconds")

    def __enter__(self) -> "Stopwatch":
        self.seconds = 0.0
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> bool:
        self.seconds = time.perf_counter() - self._t0
        return False


def stopwatch() -> Stopwatch:
    return Stopwatch()
