"""Persistent XLA compilation cache for the programs that drive the chip.

``enable_compile_cache()`` is called by entry points (``chip_smoke.py``,
``benchmarks/run.py``) before their first compile, never at import of
the library.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and nothing else is configured here; otherwise the cache lives
at the fixed ``<repo>/.jax_cache`` (a path that moves never hits, since
the directory is part of the cache's key).  Every compile is cached,
however short: the kernels of the main path compile in well under the
default one-second floor.

``CompileMeter`` counts persistent-cache hits and misses and sums the
backend compile seconds from JAX's monitoring events, so a run can
report how much of its set-up the cache saved.
"""

from __future__ import annotations

import os
import pathlib
import threading

import jax
from jax import monitoring

__all__ = ["enable_compile_cache", "default_cache_dir", "CompileMeter"]

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_COMPILE = "/jax/core/compile/backend_compile_duration"


def default_cache_dir() -> pathlib.Path:
    """``<repo>/.jax_cache`` (this file is ``<repo>/src/repro/runtime/``)."""
    return pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(default_cache_dir())
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileMeter:
    """Cache hits, misses and backend compile seconds since creation.
    JAX keeps listeners for the life of the process, so create one meter
    per program run."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.compile_s = 0.0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event in (_HIT, _MISS):
            with self._lock:
                if event == _HIT:
                    self.hits += 1
                else:
                    self.misses += 1

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == _COMPILE:
            with self._lock:
                self.compile_s += duration

    def snapshot(self) -> tuple:
        with self._lock:
            return self.hits, self.misses, self.compile_s
