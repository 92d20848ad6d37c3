"""The program's spans on the trace's clock (``chipbench/program_spans.py``)
and the readers built on them, on hand-made records and events."""
import importlib.util
from collections import namedtuple
from types import SimpleNamespace

import pytest

from chipbench import run as R
from chipbench import trace as TR
from repro.core import timing

# the fields of the program's ``repro.core.timing.Record``
Record = namedtuple("Record", "name start_ns end_ns parent thread attrs id")

OFFSET = 7_000_000_000          # program clock - trace clock, ns


@pytest.fixture
def P():
    from chipbench import program_spans
    yield program_spans
    if hasattr(timing, "tracing"):   # importing it turned recording on
        timing.tracing(False)
        timing.clear()


def reader(name):
    path = R.HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("t_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Driver:
    def __init__(self):
        self.notes = {}


def rec(i, name, a, b, parent=None, **attrs):
    """A record at trace time [a, b] (ns), stamped on the program clock."""
    return Record(name, a + OFFSET, b + OFFSET, parent, "main", attrs, i)


# trace: a window of 1000 ns, device busy [0, 50) [120, 160) [330, 360)
#   [500, 600); two benchmark steps and one admission
OPS = [("op", 0, 50), ("op", 120, 160), ("op", 330, 360), ("op", 500, 600)]
SPANS = [("chipbench.window", 0, 1000), ("chipbench.step", 100, 200),
         ("chipbench.step", 300, 400), ("chipbench.admit", 480, 700)]

RECORDS = [
    # set-up, before the trace: an earlier step of its own
    Record("step", 10, 20, None, "main", {"host_sync": 5}, 1),
    rec(2, "engine.run", 90, 900),
    rec(3, "engine.request", 95, 205, 2, rid=0),
    rec(4, "step", 100, 200, 3, rid=0),
    rec(5, "step.input", 100, 120, 4, h2d_bytes=16),
    rec(6, "step.edge", 120, 160, 4, host_sync=1),
    rec(7, "step.cloud", 160, 180, 4, host_sync=1),
    rec(8, "step.commit", 180, 200, 4, host_sync=3),
    rec(9, "engine.request", 298, 402, 2, rid=1),
    rec(10, "step", 302, 402, 9, rid=1),           # 2 ns late: a deviation
    rec(11, "step.input", 302, 330, 10),
    rec(12, "step.edge", 330, 360, 10, host_sync=1),
    rec(13, "step.cloud", 360, 380, 10, host_sync=1),
    rec(14, "step.commit", 380, 402, 10, host_sync=3),
    rec(15, "engine.observe", 470, 710, 2),
    rec(16, "sessions.admit", 480, 700, 15),
    rec(17, "engine.switch", 720, 880, 2, switch=0),
    rec(18, "pool.build", 725, 870, 17, switch=0),
    rec(19, "build.weights", 725, 765, 18, switch=0),
    rec(20, "build.exec", 765, 860, 18, switch=0, stage="cloud",
        backend_compile=1),
    Record("build.exec", 770 + OFFSET, 850 + OFFSET, None,
           "edge-stage-compile", {"cause": 18, "stage": "edge"}, 21),
]


def make_run(P, monkeypatch, records=RECORDS):
    """A traced run whose program recorded ``records``."""
    monkeypatch.setattr(P, "timing", SimpleNamespace(records=lambda: records))
    return R.Run(trace=TR.Trace(ops=list(OPS), spans=list(SPANS)),
                 driver=Driver())


def test_alignment_pairs_the_last_steps(P):
    p = P.align(RECORDS, TR.Trace(ops=OPS, spans=SPANS))
    assert p.pairs == 2 and p.deviation_p90_ns == 1.0     # offset + 1
    step = p.named("step")
    assert [(s.start, s.end) for s in step] == [(99, 199), (301, 401)]
    assert p.total(step[0], "host_sync") == 5
    assert {s.name for s in p.children[2]} == {
        "engine.request", "engine.observe", "engine.switch"}


def test_misaligned_or_missing_reads_nothing(P, monkeypatch):
    late = [r._replace(start_ns=r.start_ns + 200_000) if r.id == 10 else r
            for r in RECORDS]
    assert P.align(late, TR.Trace(ops=OPS, spans=SPANS)) is None
    # fewer program steps than benchmark spans: no pairing
    assert P.align(RECORDS[4:], TR.Trace(ops=OPS, spans=SPANS)) is None
    assert P.align([], TR.Trace(ops=OPS, spans=SPANS)) is None
    assert P.align(RECORDS, None) is None
    run = make_run(P, monkeypatch, late)
    for name in ("step_roundtrip_ms", "step_host_syncs", "admit_host_ms",
                 "loop_host_ms", "build_weights_ms", "build_exec_ms",
                 "step_host_syncs.frame"):
        assert reader(name)(run) is None, name
    assert "program_spans" not in run.driver.notes


def test_readers(P, monkeypatch):
    run = make_run(P, monkeypatch)
    # on the trace's clock (offset + 1): device idle inside step.input
    # [99,119) [301,329) and step.commit [179,199) [379,401)
    assert reader("step_roundtrip_ms")(run) == pytest.approx(
        (20 + 28 + 20 + 22) * 1e-6 / 2)
    assert reader("step_host_syncs")(run) == 5
    assert reader("step_host_syncs.frame")(run) == 5
    # admission [479, 699): busy [500, 600) inside
    assert reader("admit_host_ms")(run) == pytest.approx(120e-6)
    # engine.run [89, 899) minus its children [94,204) [297,401) [469,709)
    # [719,879): idle 5+93+68+10+20 = 196 (busy 0..50 lies before it)
    assert reader("loop_host_ms")(run) == pytest.approx(196e-6 / 2)
    assert reader("build_weights_ms")(run) == pytest.approx(40e-6)
    # cloud [764, 859) and edge [769, 849) overlap: 95 ns covered
    assert reader("build_exec_ms")(run) == pytest.approx(95e-6)
    notes = run.driver.notes["program_spans"]
    assert notes["pairs"] == 2 and notes["repartitions"] == 1
    assert notes["build_exec_counts"]["backend_compile"] == 1
    idle = dict(notes["idle_by_program_span"])
    assert idle["sessions.admit"] == pytest.approx(120e-9)
    prog, bench = (notes["idle_in_step_s"][k]
                   for k in ("program", "benchmark"))
    assert prog == pytest.approx(bench, rel=0.05)


def test_a_program_without_spans_reads_nothing(P, monkeypatch):
    run = make_run(P, monkeypatch)
    monkeypatch.setattr(P, "timing", None)
    assert reader("step_host_syncs")(run) is None
    assert reader("build_exec_ms")(run) is None
