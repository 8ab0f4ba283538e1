"""Persistent compilation cache placement for the repo's entry points.

``enable_compile_cache()`` is called first thing by ``chip_smoke.py``,
``repro.launch.serve_fleet.main`` and ``benchmarks/run.py`` — never on
import, so library users keep full control of jax's configuration.

- When ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it; the
  helper sets no other directory.
- Otherwise the cache lives at a fixed directory inside the checkout
  (``.jax_cache/``, gitignored). The path is part of the cache key, so
  it never carries a temp name, a pid or a time.

``CacheCounter`` counts the cache's hit and miss events, so a second
run can show that it compiled nothing new.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # the kernels compile in about a second each: cache them too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CacheCounter:
    """Counts persistent-cache hits and misses (writes) from the moment
    it is created."""

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1
