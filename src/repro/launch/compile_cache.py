"""JAX's persistent compilation cache for this checkout.

``enable_compile_cache()`` is called by the entry points
(``chipbench/run.py``, ``chip_smoke.py`` and the ``repro.launch`` mains)
before their first compile, never at import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that directory and
no other is set here.  Otherwise the cache goes to
``<checkout>/.jax-cache``: a fixed path, because the path is what lets a
later process find an entry; the same one CI uses; and listed in
``.gitignore``.

With the cache on, a ``fresh=True`` ("new container") stage build still
retraces and recompiles, but XLA's compile can be served from disk —
so a cold build that hits the cache is a different, cheaper event than
one that misses.  ``CacheEvents`` counts which one a build was; the same
listener counts each event in the program's spans (``repro.core.timing``).
"""
from __future__ import annotations

import os
import threading
from pathlib import Path

import jax

from repro.core import timing

CHECKOUT = Path(__file__).resolve().parents[3]

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


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


# process totals of the persistent cache's events, kept by the one
# listener below
_counts = {"hit": 0, "miss": 0}
_counts_lock = threading.Lock()     # events arrive on compiling threads
_listening = False


def _on_event(event: str, **_kw) -> None:
    kind = "hit" if event == _HIT else "miss" if event == _MISS else None
    if kind is not None:
        with _counts_lock:
            _counts[kind] += 1
        timing.count("compile_cache." + kind)


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == _BACKEND_COMPILE:
        timing.count("backend_compile")


def _listen() -> None:
    """Register the one listener of JAX's compile events (idempotent):
    it keeps the process totals ``CacheEvents`` reads and counts each
    event (``compile_cache.hit``/``.miss``, ``backend_compile``) in the
    span open on the compiling thread."""
    global _listening
    with _counts_lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


class CacheEvents:
    """Running counts of persistent-cache hits and misses in this process
    since this object was made (JAX's monitoring events); diff two
    ``counts()`` around a build."""

    def __init__(self):
        _listen()
        self._base = self._totals()

    @staticmethod
    def _totals():
        with _counts_lock:
            return _counts["hit"], _counts["miss"]

    def counts(self):
        hits, misses = self._totals()
        return hits - self._base[0], misses - self._base[1]
