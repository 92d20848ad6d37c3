"""The reduction from a profiler trace to busy time, idle gaps and
per-span device time, on hand-made events and on a recorded trace."""
import pytest

from chipbench import trace as TR

OPS = [("a", 0, 10), ("b", 5, 15), ("a", 20, 30), ("k", 40, 45)]
SPANS = [("chipbench.window", 0, 50), ("chipbench.step", 0, 16),
         ("chipbench.step", 18, 32), ("chipbench.admit", 35, 50)]


def test_union_merges_and_clips():
    assert TR.union([(0, 10), (5, 15), (20, 30)]) == [(0, 15), (20, 30)]
    assert TR.union([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_busy_and_idle_share():
    assert TR.busy_ns(OPS, 0, 50) == 15 + 10 + 5
    assert TR.idle_gaps(OPS, 0, 50) == [(15, 20), (30, 40), (45, 50)]
    assert TR.busy_ns(OPS, 0, 50) + sum(b - a for a, b in
                                        TR.idle_gaps(OPS, 0, 50)) == 50


def test_busy_inside_spans_counts_overlap_once():
    steps = [s for s in SPANS if s[0] == "chipbench.step"]
    assert TR.busy_in_spans(OPS, steps) == 15 + 10
    # a span repeated (nested) is not counted twice
    assert TR.busy_in_spans(OPS, steps + steps[:1]) == 25


def test_device_ms_per_span():
    tr = TR.Trace(ops=OPS, spans=SPANS)
    assert TR.device_ms_per_span(tr, "step") == pytest.approx(25e-6 / 2)
    assert TR.device_ms_per_span(tr, "admit") == pytest.approx(5e-6)
    assert TR.device_ms_per_span(tr, "frame") is None
    assert TR.device_ms_per_span(None, "step") is None


def test_op_time_and_top_ops():
    assert TR.op_time_ns(OPS, lambda n: n == "a", 0, 50) == (20, 2)
    top = TR.top_ops(OPS, 0, 50)
    assert top[0] == ["a", 20e-9] and [n for n, _ in top] == ["a", "b", "k"]


def test_ops_named_by_their_instruction_text():
    """On the TPU the trace names an operation by its HLO text; a loop's
    event spans its children, which are listed too."""
    ops = [("%while.19 = (s32[]{:T(128)}, f32[4]) while(%t), body=%b", 0, 30),
           ("%fn.3 = f32[4,2048]{1,0:T(4,128)} custom-call(%x), "
            "custom_call_target=\"tpu_custom_call\"", 0, 10),
           ("%fusion.7 = bf16[24,2048]{1,0:T(8,128)(2,1)} fusion(%y)", 10, 30)]
    assert TR.op_id(ops[1][0]) == "fn.3" and TR.op_id("a") == "a"
    assert TR.op_time_ns(ops, {"fn.3"}.__contains__, 0, 50) == (10, 1)
    assert TR.top_ops(ops, 0, 50) == [["fusion.7 bf16[24,2048]", 20e-9],
                                      ["fn.3 f32[4,2048]", 10e-9]]


def test_idle_charged_to_innermost_host_span():
    got = dict(TR.idle_by_span(OPS, SPANS, 0, 50))
    # 15..16 in a step, 16..18 outside, 18..20 in the second step,
    # 30..32 step, 32..35 outside, 35..40 and 45..50 in the admission
    assert got == pytest.approx({"step": 5e-9, "outside": 5e-9,
                                 "admit": 10e-9})


def test_window_span_required():
    tr = TR.Trace(ops=OPS, spans=SPANS[1:])
    with pytest.raises(ValueError):
        tr.window()
    assert TR.Trace(ops=OPS, spans=SPANS).window() == (0, 50)


def test_recorded_trace_yields_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    spans = TR.Spans(True)
    TR.start(str(tmp_path))
    with spans("window"):
        for _ in range(3):
            with spans("step"):
                sum(i * i for i in range(100))
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = TR.load(str(tmp_path))
    lo, hi = tr.window()
    steps = tr.spans_named("step")
    assert len(steps) == 3 and all(lo <= s[1] < s[2] <= hi for s in steps)
    # the Python tracer is off: no event of a Python call was recorded
    from jax.profiler import ProfileData
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = {ev.name for pl in ProfileData.from_file(str(path)).planes
             for ln in pl.lines for ev in ln.events}
    assert not any(n.startswith("$") for n in names)


def test_spans_off_cost_nothing():
    import contextlib
    assert isinstance(TR.Spans(False)("x"), contextlib.nullcontext)
