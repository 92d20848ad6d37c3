"""Sanctioned wall-clock measurement primitives, and the program's spans
and counters.

Every wall measurement in the serving/switching path routes through this
module (or through ``repro.serving.clock``); raw ``time.perf_counter()``
anywhere else in ``src/`` is an NK02 finding (``repro.analysis``).  The
point is auditability: downtime numbers are only trustworthy if every
timer either feeds the stream ``Clock`` (deterministic under
``VirtualClock``) or is a deliberate, greppable wall site.

* ``span(name, **attrs)`` — one named boundary of the program (a build,
  a hand-off, a decode step's stage).  While recording is on it is kept
  as a ``Record`` and shown in the profiler; ``timed=True`` spans also
  feed a report field (``BuildReport``, ``HandoffReport``,
  ``RequestTiming``) from the yielded ``Measurement.wall``, on or off.
* ``count(name, n)`` — a counter (host syncs, bytes copied, engine
  events, compiles), credited to the innermost open span.
* ``Stopwatch`` — span timing across non-contiguous code (start here,
  read elapsed there): the ``t_begin``/``t_blocked`` pattern in the
  switch strategies.
* ``measure()`` — context-managed block timing; pass ``charge_to=clock``
  to replay the measured wall onto a stream clock on exit
  (``Clock.measure()`` is the bound convenience form).
* ``now()`` — a monotonic wall timestamp for deadlines on *real* thread
  waits (build drains, handle timeouts), which stay wall-time by nature
  even under a virtual stream clock.

Recording (``tracing(True)``; off by default) stamps spans with
``time.time_ns()``, the clock of JAX's profiler, so a record lines up
with the device operations of a trace taken at the same time; each span
also opens ``jax.profiler.TraceAnnotation("nk." + name)``.  Off, a span
that feeds no report is one flag check and a shared no-op context, and a
timed one costs what a ``Stopwatch`` does.  Records stay in memory
(``records()``, ``clear()``).
"""
from __future__ import annotations

import itertools
import threading
import time
from contextlib import contextmanager
from typing import Callable, Iterator, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


def now() -> float:
    """Monotonic wall timestamp (seconds): deadlines on real thread waits."""
    return time.perf_counter()


class Stopwatch:
    """Wall-clock span timer: created running, read via ``elapsed()``."""

    __slots__ = ("_t0",)

    def __init__(self):
        self._t0 = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self._t0

    def restart(self) -> float:
        """Read the current span and start a new one."""
        t = time.perf_counter()
        dt = t - self._t0
        self._t0 = t
        return dt


class Measurement:
    """Result box for ``measure()`` and ``span()``: ``wall`` is valid
    after the block."""

    __slots__ = ("wall",)

    def __init__(self):
        self.wall = 0.0


@contextmanager
def measure(charge_to=None) -> Iterator[Measurement]:
    """Time a block; optionally charge the measured wall to a stream clock.

    ``charge_to`` is any object with ``charge(dt)`` — a
    ``repro.serving.clock.Clock``.  The charge happens even if the block
    raises: a failed switch still blocked the stream for as long as it
    ran.
    """
    m = Measurement()
    sw = Stopwatch()
    try:
        yield m
    finally:
        m.wall = sw.elapsed()
        if charge_to is not None:
            charge_to.charge(m.wall)


# ---------------------------------------------------------------------------
# spans and counters
# ---------------------------------------------------------------------------

class Record(NamedTuple):
    """One closed span.  ``start_ns``/``end_ns`` are ``time.time_ns()``
    stamps (the profiler's clock); ``parent`` is the ``id`` of the
    innermost span open on the same thread when it opened; ``attrs`` holds
    the span's attributes, the ``rid``/``switch`` it inherits, a ``cause``
    (the id of the span on another thread that started its work) and the
    counter increments made while it was the innermost open span."""
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]
    thread: str
    attrs: dict
    id: int

    @property
    def wall(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


# attributes a span passes on to every span opened inside it, on its own
# thread or (through ``carry``) on another: the request and the
# repartition the work belongs to
_INHERITED = ("rid", "switch")

_on = False
_records: List[Record] = []
_ids = itertools.count(1)
_local = threading.local()


class _Frame:
    __slots__ = ("id", "attrs")

    def __init__(self, id_: int, attrs: dict):
        self.id = id_
        self.attrs = attrs


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Noop:
    """The shared context of an untimed span while recording is off."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


class _Timed:
    """A timed span while recording is off: a ``Stopwatch``'s cost."""
    __slots__ = ("m", "t0")

    def __enter__(self) -> Measurement:
        self.m = Measurement()
        self.t0 = time.perf_counter()
        return self.m

    def __exit__(self, *exc):
        self.m.wall = time.perf_counter() - self.t0
        return False


class _Span:
    """A span while recording is on."""
    __slots__ = ("name", "frame", "parent", "m", "ann", "t0")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        stack = _stack()
        up = stack[-1] if stack else getattr(_local, "cause", None)
        if up is not None:
            if not stack:
                attrs["cause"] = up.id
            for k in _INHERITED:
                if k not in attrs and k in up.attrs:
                    attrs[k] = up.attrs[k]
        self.parent = stack[-1].id if stack else None
        self.frame = _Frame(next(_ids), attrs)

    def __enter__(self) -> Measurement:
        _stack().append(self.frame)
        self.m = Measurement()
        self.ann = jax.profiler.TraceAnnotation("nk." + self.name)
        self.ann.__enter__()
        self.t0 = time.time_ns()
        return self.m

    def __exit__(self, *exc):
        t1 = time.time_ns()
        self.ann.__exit__(*exc)
        stack = _stack()
        if stack and stack[-1] is self.frame:
            stack.pop()
        self.m.wall = (t1 - self.t0) * 1e-9
        _records.append(Record(self.name, self.t0, t1, self.parent,
                               threading.current_thread().name,
                               self.frame.attrs, self.frame.id))
        return False


def tracing(on: bool) -> None:
    """Turn recording of spans and counters on or off (off by default)."""
    global _on
    _on = bool(on)


def span(name: str, timed: bool = False, **attrs):
    """Context manager for one named boundary; yields its ``Measurement``.

    ``timed=True``: a report field reads ``.wall``, so the block is timed
    whether or not recording is on.  Otherwise, with recording off, this
    is a shared no-op context that yields None."""
    if _on:
        return _Span(name, attrs)
    return _Timed() if timed else _NOOP


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` in the innermost span open on this
    thread (outside every span, the count is dropped)."""
    if not _on:
        return
    stack = getattr(_local, "stack", None)
    if stack:
        a = stack[-1].attrs
        a[name] = a.get(name, 0) + n


def carry(fn: Callable) -> Callable:
    """``fn``, to be run on another thread: the spans it opens there with
    no parent record the span open here now as their ``cause`` and
    inherit its ``rid``/``switch``.  Off, or outside any span, ``fn``
    itself."""
    stack = getattr(_local, "stack", None) if _on else None
    if not stack:
        return fn
    cause = stack[-1]

    def run(*args, **kwargs):
        prev = getattr(_local, "cause", None)
        _local.cause = cause
        try:
            return fn(*args, **kwargs)
        finally:
            _local.cause = prev
    return run


def records() -> List[Record]:
    """The spans closed while recording was on, in closing order."""
    return list(_records)


def clear() -> None:
    """Forget every record."""
    del _records[:]


# -- counted host <-> device traffic ------------------------------------------

def block(tree):
    """``jax.block_until_ready``: one host sync."""
    if _on:
        count("host_sync")
    return jax.block_until_ready(tree)


def fetch(x) -> np.ndarray:
    """``np.asarray``; of a device array, one host sync and its bytes."""
    a = np.asarray(x)
    if _on and isinstance(x, jax.Array):
        count("host_sync")
        count("d2h_bytes", a.nbytes)
    return a


def fetch_all(tree):
    """``jax.device_get`` of a pytree: every leaf's copy started before
    any is waited on, so one host sync for all of them, and their bytes."""
    out = jax.device_get(tree)
    if _on:
        count("host_sync")
        count("d2h_bytes", sum(np.asarray(a).nbytes
                               for a in jax.tree_util.tree_leaves(out)))
    return out


def upload(x, dtype=None):
    """``jnp.asarray`` of host data; its bytes counted."""
    a = jnp.asarray(x, dtype)
    if _on and not isinstance(x, jax.Array):
        count("h2d_bytes", a.nbytes)
    return a


def upload_all(tree):
    """``jax.device_put`` of a pytree of host arrays, in one call; their
    bytes counted."""
    out = jax.device_put(tree)
    if _on:
        count("h2d_bytes", sum(a.nbytes
                               for a in jax.tree_util.tree_leaves(out)))
    return out
