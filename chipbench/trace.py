"""Profiler trace -> device busy time, idle gaps and per-span device time.

``Trace`` holds two lists read from the profiler's ``.xplane.pb``: device
operations ``(name, start_ns, end_ns)`` from the ``XLA Ops`` line of each
device plane, and the benchmark's own host spans (``chipbench.*``
``TraceAnnotation``s) from the host plane.  Everything else is interval
arithmetic on those lists, so it is tested on hand-made events.
"""
from __future__ import annotations

import base64
import contextlib
import glob
import re
from dataclasses import dataclass, field
from typing import List, Tuple

SPAN_PREFIX = "chipbench."
DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)


class Spans:
    """Host spans for the trace; free when the run is not traced."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


@dataclass
class Trace:
    ops: List[Event] = field(default_factory=list)
    spans: List[Event] = field(default_factory=list)
    n_devices: int = 1

    def spans_named(self, name: str) -> List[Event]:
        full = SPAN_PREFIX + name
        return [s for s in self.spans if s[0] == full]

    def window(self) -> Tuple[float, float]:
        """The traced window: the ``chipbench.window`` span."""
        w = self.spans_named("window")
        if not w:
            raise ValueError("trace holds no chipbench.window span")
        return w[0][1], w[0][2]


def start(directory: str) -> None:
    """Start the profiler with its Python tracer off.  That tracer records
    every Python call of the serving loop: it would swamp the trace and
    slow the window it measures.  The benchmark's spans are annotations
    of the host tracer, which stays on."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=opts)


def load(directory: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(files[-1])
    tr = Trace()
    devices = 0
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if lines:
                devices += 1
            for ln in lines:
                for ev in ln.events:
                    tr.ops.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns))
        else:
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        tr.spans.append((ev.name, ev.start_ns,
                                         ev.start_ns + ev.duration_ns))
    tr.n_devices = max(devices, 1)
    tr.ops.sort(key=lambda e: e[1])
    tr.spans.sort(key=lambda e: e[1])
    return tr


def union(intervals, lo: float = float("-inf"),
          hi: float = float("inf")) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` intervals, clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for a, b in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(ops: List[Event], lo: float, hi: float) -> float:
    """Time in ``[lo, hi]`` in which some operation ran on the device(s)."""
    return sum(b - a for a, b in union(((s, e) for _, s, e in ops), lo, hi))


def busy_in_spans(ops: List[Event], spans: List[Event]) -> float:
    """Device busy time inside the given host spans (spans that overlap
    are counted once)."""
    merged = union((s, e) for _, s, e in spans)
    busy = union((s, e) for _, s, e in ops)
    total, j = 0.0, 0
    for a, b in merged:
        while j < len(busy) and busy[j][1] <= a:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < b:
            total += min(b, busy[k][1]) - max(a, busy[k][0])
            k += 1
    return total


def device_ms_per_span(trace: "Trace", name: str):
    """Device busy milliseconds inside the host spans ``name`` that start
    in the traced window, per span; None where there are none."""
    if trace is None or not trace.ops:
        return None
    lo, hi = trace.window()
    spans = [s for s in trace.spans_named(name) if lo <= s[1] <= hi]
    if not spans:
        return None
    return busy_in_spans(trace.ops, spans) * 1e-6 / len(spans)


def idle_gaps(ops: List[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Intervals of ``[lo, hi]`` in which nothing ran on the device."""
    gaps, t = [], lo
    for a, b in union(((s, e) for _, s, e in ops), lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def op_id(name: str) -> str:
    """The HLO name of a device operation: the trace names an operation by
    its instruction text, ``%fusion.12 = f32[...] fusion(...)``."""
    if name.startswith("%"):
        return name[1:].split(" = ", 1)[0]
    return name


def op_label(name: str) -> str:
    """A short label: the HLO name and the result's type, without its
    layout (``fusion.12 f32[224,8,29,64]``)."""
    head, _, rest = name.partition(" = ")
    return f"{op_id(name)} {rest.split('{', 1)[0]}".strip() if rest else name


# operations that only hold others (a loop, a branch, a call): their time
# is their children's, which the trace lists too
_CONTAINERS = ("while", "conditional", "call")


def op_time_ns(ops: List[Event], match, lo: float, hi: float) -> Tuple[float, int]:
    """Summed duration and count of operations whose HLO name
    (``op_id``) satisfies ``match``, starting inside ``[lo, hi]``."""
    tot, n = 0.0, 0
    for name, s, e in ops:
        if lo <= s <= hi and match(op_id(name)):
            tot += e - s
            n += 1
    return tot, n


def top_ops(ops: List[Event], lo: float, hi: float, n: int = 10) -> list:
    """``[[label, seconds]]`` of the operations that took most time, loops
    and other containers left out (their children are counted)."""
    acc: dict = {}
    for name, s, e in ops:
        if lo <= s <= hi and not op_id(name).startswith(_CONTAINERS):
            key = op_label(name)
            acc[key] = acc.get(key, 0.0) + (e - s)
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_by_span(ops: List[Event], spans: List[Event], lo: float, hi: float,
                 n: int = 10) -> list:
    """``[[what the host was doing, seconds]]`` for the device's idle
    time: each idle stretch is charged to the innermost host span open
    over it (``outside`` where none is), longest total first."""
    acc: dict = {}
    segs = _innermost(s for s in spans if s[0] != SPAN_PREFIX + "window")
    j = 0
    for a, b in idle_gaps(ops, lo, hi):
        t = a
        while j < len(segs) and segs[j][1] <= t:
            j += 1
        while t < b:
            if j < len(segs) and segs[j][0] <= t:
                name, stop = segs[j][2], min(b, segs[j][1])
            else:
                name = "outside"
                stop = min(b, segs[j][0]) if j < len(segs) else b
            acc[name] = acc.get(name, 0.0) + (stop - t)
            t = stop
            if j < len(segs) and segs[j][1] <= t:
                j += 1
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def _innermost(spans) -> List[Tuple[float, float, str]]:
    """Non-overlapping ``(start, end, name)`` segments, each labelled with
    the latest-starting host span open over it."""
    marks = []
    for i, (name, s, e) in enumerate(spans):
        if e > s:
            marks.append((s, 1, i, name))
            marks.append((e, 0, i, name))
    marks.sort(key=lambda m: (m[0], m[1]))
    open_: dict = {}
    out: List[Tuple[float, float, str]] = []
    prev = None
    for t, is_start, i, name in marks:
        if open_ and prev is not None and t > prev:
            label = open_[max(open_, key=lambda k: open_[k][0])][1]
            out.append((prev, t, label[len(SPAN_PREFIX):]))
        if is_start:
            open_[i] = (t, name)
        else:
            open_.pop(i, None)
        prev = t
    return out


_CALL = re.compile(r"%([\w.\-]+) = .*custom_call_target=\"tpu_custom_call\"")
_B64 = re.compile(r"[A-Za-z0-9+/]{40,}={0,2}")


def kernel_op_names(executables, marker: bytes) -> set:
    """Names of the Pallas custom calls in compiled ``executables`` whose
    serialized kernel mentions ``marker`` (a kernel function's name).
    XLA names such a call after the jitted function around it, so the
    name is read from the program's executables, not guessed."""
    names = set()
    for exe in executables:
        for line in exe.as_text().splitlines():
            m = _CALL.search(line)
            if not m:
                continue
            for blob in _B64.findall(line):
                try:
                    raw = base64.b64decode(blob + "=" * (-len(blob) % 4))
                except ValueError:
                    continue
                if marker in raw:
                    names.add(m.group(1))
                    break
    return names
