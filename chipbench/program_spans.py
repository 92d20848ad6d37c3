"""The program's own spans and counters (``repro.core.timing``) on the
clock of the benchmark's profiler trace, for the per-layer readers.

Importing this module turns the program's recording on.  ``run.py``
loads the per-layer readers, and with them this module, only for
``--trace 1`` and before set-up, so only a traced run records; the
untraced run that gives the end-to-end metrics never does.  A program
without the facility records nothing, and every reader returns None.

Alignment: the trace keeps the benchmark's ``step`` (LM) or ``frame``
(CNN) spans, each around one call of the pipeline's ``process``, in
nanoseconds from the profile's start.  The program's ``step`` span opens
at the top of that call, stamped with ``time.time_ns()``.  So the
trace's N spans pair with the program's last N ``step`` records, and
the median of the pairs' start differences is the offset between the
clocks.  Where the p90 of the pairs' |deviation| from it exceeds
``TOLERANCE_NS`` nothing is read: a misaligned reading is no reading.
The program's spans are then moved onto the trace's clock and clipped
to its ``window``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from chipbench import trace as TR

try:
    from repro.core import timing
except ImportError:                 # a checkout without the program
    timing = None
if hasattr(timing, "tracing"):
    timing.tracing(True)

TOLERANCE_NS = 50_000


@dataclass
class Span:
    """A program span on the trace's clock, clipped to the window."""
    name: str
    start: float
    end: float
    id: int
    parent: Optional[int]
    attrs: dict

    @property
    def wall_ns(self) -> float:
        return self.end - self.start


@dataclass
class Program:
    spans: List[Span]
    lo: float
    hi: float
    pairs: int
    deviation_p90_ns: float
    children: Dict[int, List[Span]] = field(default_factory=dict)

    def __post_init__(self):
        for s in self.spans:
            if s.parent is not None:
                self.children.setdefault(s.parent, []).append(s)

    def named(self, *names: str) -> List[Span]:
        return [s for s in self.spans if s.name in names]

    def total(self, span: Span, counter: str) -> int:
        """A counter's increments in ``span`` and the spans inside it on
        its thread."""
        return span.attrs.get(counter, 0) + sum(
            self.total(c, counter) for c in self.children.get(span.id, ()))


def align(records, trace) -> Optional[Program]:
    """The program's records on the trace's clock, or None where they do
    not pair with the trace's ``step``/``frame`` spans."""
    if trace is None or not records:
        return None
    bench = trace.spans_named("step") or trace.spans_named("frame")
    steps = sorted((r for r in records if r.name == "step"),
                   key=lambda r: r.start_ns)
    n = len(bench)
    if n == 0 or len(steps) < n:
        return None
    diff = np.asarray([p.start_ns - b[1] for p, b in zip(steps[-n:], bench)],
                      np.float64)
    offset = float(np.median(diff))
    dev = float(np.percentile(np.abs(diff - offset), 90))
    if dev > TOLERANCE_NS:
        return None
    lo, hi = trace.window()
    spans = []
    for r in records:
        a, b = max(r.start_ns - offset, lo), min(r.end_ns - offset, hi)
        if a <= b:
            spans.append(Span(r.name, a, b, r.id, r.parent, r.attrs))
    spans.sort(key=lambda s: s.start)
    return Program(spans, lo, hi, n, dev)


def program(run) -> Optional[Program]:
    """``align`` for this run, computed once; its alignment and the idle
    time by innermost program span go on the run's info line."""
    if "_program" not in run.__dict__:
        records = timing.records() if hasattr(timing, "records") else []
        run._program = p = align(records, run.trace)
        info = getattr(run.driver, "notes", None)
        if p is not None and info is not None:
            info["program_spans"] = notes(p, run.trace)
    return run._program


def host_syncs_per_step(run) -> Optional[float]:
    """The ``host_sync`` counter inside the program's ``step`` spans (a
    decode step or a frame, children included), per step."""
    p = program(run)
    steps = p.named("step") if p is not None else []
    if not steps:
        return None
    return sum(p.total(s, "host_sync") for s in steps) / len(steps)


def idle_ns(trace, spans: List[Span]) -> float:
    """Device idle time inside the union of ``spans``."""
    covered = sum(b - a for a, b in TR.union((s.start, s.end)
                                             for s in spans))
    return covered - TR.busy_in_spans(
        trace.ops, [(s.name, s.start, s.end) for s in spans])


def notes(p: Program, trace) -> dict:
    """What the readers do not report: the alignment, the device's idle
    seconds inside the program's and the benchmark's step spans and by
    innermost program span, and the compile counters of the builds'
    stages."""
    events = [(TR.SPAN_PREFIX + s.name, s.start, s.end) for s in p.spans]
    compiles = {}
    for s in p.named("build.exec"):
        for k in ("compile_cache.hit", "compile_cache.miss",
                  "backend_compile"):
            compiles[k] = compiles.get(k, 0) + p.total(s, k)
    bench = [(n, max(a, p.lo), min(b, p.hi)) for n, a, b in
             trace.spans_named("step") or trace.spans_named("frame")]
    bench_idle = sum(b - a for a, b in TR.union(
        (a, b) for _, a, b in bench)) - TR.busy_in_spans(trace.ops, bench)
    return {"pairs": p.pairs, "deviation_p90_us": p.deviation_p90_ns * 1e-3,
            "idle_in_step_s": {"program": idle_ns(trace, p.named("step"))
                               * 1e-9, "benchmark": bench_idle * 1e-9},
            "idle_by_program_span": TR.idle_by_span(trace.ops, events,
                                                    p.lo, p.hi, n=16),
            "build_exec_counts": compiles,
            "repartitions": len(p.named("engine.switch"))}
