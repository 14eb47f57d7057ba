"""Where JAX's persistent compilation cache lives.

One rule for every entry script (chip_smoke.py, bench.py's children,
__graft_entry__.py): call ``configure()`` before the first compile.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing is
  chosen here. The machine's owner placed the cache (the chip tool may
  keep one directory alive across calls) and entries appear there and
  nowhere else.
* not set: the cache is ``<checkout>/.jax_cache`` (git-ignored). The
  path is part of the cache key, so it is a fixed place under the
  checkout, never a temporary name.

Either way the min-size / min-compile-time thresholds are lowered so
every executable is cached: a cold ResNet-50 train step is tens of
seconds, but the serving buckets and the kernels are small and would
fall under JAX's one-second default.

``runtime/aot.py`` is the in-process layer above this (signature table,
``warm()``, ``CompileWatch``); it keeps nothing on disk.
"""

from __future__ import annotations

import os

import jax

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def default_dir():
    """``<checkout>/.jax_cache``: beside the package directory."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(here)),
                        ".jax_cache")


def configure():
    """Point JAX's persistent compilation cache (module docstring) and
    cache every executable. Returns the directory in use."""
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", default_dir())
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir


class PersistentCacheWatch:
    """Counts JAX's persistent-cache hits and misses over a region, from
    the events JAX itself records (``jax.monitoring``): ``hits`` are
    executables loaded from the cache directory, ``misses`` are
    compiles XLA paid and then stored. ``compile_seconds`` is the wall
    JAX spent obtaining executables either way (XLA compile on a miss,
    load on a hit) — the number that shrinks when the cache is warm."""

    def __init__(self):
        self.hits = 0
        self.misses = 0
        self.compile_seconds = 0.0

    def _on_event(self, event, **_):
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1

    def _on_duration(self, event, seconds, **_):
        if event == _BACKEND_COMPILE:
            self.compile_seconds += seconds

    def __enter__(self):
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(
            self._on_duration)
        return False
