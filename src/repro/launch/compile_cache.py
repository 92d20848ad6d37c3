"""JAX's persistent compilation cache for this checkout.

``enable_compile_cache()`` is called by the entry points
(``chip_smoke.py`` and the ``repro.launch`` mains) before their first
compile, never at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already uses that directory and no other is set here.  Otherwise the
cache goes to ``<checkout>/.jax-cache``: a fixed path, because the path
is what lets a later process find an entry; the same one CI uses; and
listed in ``.gitignore``.

With the cache on, a ``fresh=True`` ("new container") stage build still
retraces and recompiles, but XLA's compile can be served from disk —
so a cold build that hits the cache is a different, cheaper event than
one that misses.  ``CacheEvents`` counts which one a build was.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax-cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every compile, not only those over a second: a stage build is
    # what a cold switch waits on, however short
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CacheEvents:
    """Running counts of persistent-cache hits and misses in this process
    (JAX's monitoring events); diff two ``counts()`` around a build."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1

    def counts(self):
        return self.hits, self.misses
