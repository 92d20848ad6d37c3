"""The program's spans and counters (``repro.core.timing``).

* off (the default): nothing is recorded, and the report fields the
  timed spans feed are still measured;
* on: parent, thread and ``cause`` links, inherited ids, counters in the
  innermost span;
* a decode step and a CNN frame record their stage spans and host syncs;
* a ``switch_b1`` records engine -> pool build -> weights/executables,
  a ``switch_b2`` hand-off its export/import or recompute, and the
  reports read the same walls as the spans;
* the spans share the profiler's clock.
"""
import dataclasses
import glob
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import CNNLayer
from repro.core import NetworkModel, PipelineManager, timing
from repro.core.stages import CnnStageRunner, StageRunner
from repro.launch.compile_cache import CacheEvents
from repro.models import transformer as T
from repro.serving import (ServingEngine, VirtualClock, make_session_manager,
                           request_stream)


@pytest.fixture
def rec():
    """Recording on for one test; off and empty afterwards."""
    timing.clear()
    timing.tracing(True)
    yield timing
    timing.tracing(False)
    timing.clear()


def named(name, records=None):
    return [r for r in (records or timing.records()) if r.name == name]


def one(name):
    rs = named(name)
    assert len(rs) == 1, (name, rs)
    return rs[0]


def total(span, counter, records):
    kids = [r for r in records if r.parent == span.id]
    return span.attrs.get(counter, 0) + sum(total(k, counter, records)
                                            for k in kids)


@pytest.fixture(scope="module")
def lm():
    cfg = dataclasses.replace(get_config("qwen2.5-3b").reduced(),
                              num_layers=2)
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                              cfg.vocab_size)
    return cfg, params, {"tokens": toks}


@pytest.fixture(scope="module")
def cnn():
    layers = (CNNLayer("conv", out_ch=8, kernel=3), CNNLayer("pool", stride=2),
              CNNLayer("conv", out_ch=8, kernel=3), CNNLayer("pool", stride=2),
              CNNLayer("flatten"), CNNLayer("dense", units=10))
    cfg = dataclasses.replace(get_config("vgg19"), input_hw=16, input_ch=3,
                              layers=layers, num_classes=10)
    runner = CnnStageRunner(cfg)
    img = {"image": jnp.asarray(np.random.default_rng(0).standard_normal(
        (1, 16, 16, 3), dtype=np.float32))}
    return runner, img


def _sessions(lm, **kw):
    cfg, params, _ = lm
    mgr, sm = make_session_manager(cfg, params, split=1,
                                   net=NetworkModel(20.0), num_slots=2,
                                   max_seq=32, **kw)
    rng = np.random.default_rng(0)
    for n in (5, 7):
        sm.admit(rng.integers(0, cfg.vocab_size, n).astype(np.int32))
    return mgr, sm


# ---------------------------------------------------------------------------
# the facility
# ---------------------------------------------------------------------------

def test_off_records_nothing_and_timed_spans_still_measure():
    with timing.span("untimed", split=1) as m:
        timing.count("host_sync")
    assert m is None
    with timing.span("timed", timed=True) as m:
        time.sleep(0.002)
    assert m.wall >= 0.002
    assert timing.carry(test_off_records_nothing_and_timed_spans_still_measure) \
        is test_off_records_nothing_and_timed_spans_still_measure
    assert timing.records() == []


def test_off_switch_reports_measured_without_records(lm):
    cfg, params, inputs = lm
    mgr = PipelineManager(StageRunner(cfg, params), split=1,
                          net=NetworkModel(20.0), sample_inputs=inputs)
    rep = mgr.repartition("switch_b1", 2)
    _, req = mgr.serve(inputs)
    mgr.close()
    assert rep.t_build > 0 and rep.build_detail.t_weights > 0
    assert rep.t_build == rep.build_detail.t_wall
    assert req.t_edge > 0 and req.t_cloud > 0
    assert timing.records() == []


def test_links_ids_and_counters_land_in_the_innermost_span(rec):
    def remote():
        with timing.span("remote"):
            timing.count("n")

    with timing.span("outer", rid=7) as m:
        timing.count("n")
        with timing.span("inner"):
            timing.count("n", 2)
            timing.count("bytes", 10)
        th = threading.Thread(target=timing.carry(remote), name="helper")
        th.start()
        th.join(30)
    assert not th.is_alive()
    with timing.span("later"):
        pass
    outer, inner, far = one("outer"), one("inner"), one("remote")
    me = threading.current_thread().name
    assert outer.parent is None and inner.parent == outer.id
    assert outer.thread == inner.thread == me and far.thread == "helper"
    assert far.parent is None and far.attrs["cause"] == outer.id
    assert inner.attrs["rid"] == far.attrs["rid"] == 7
    assert "cause" not in one("later").attrs and "rid" not in one("later").attrs
    assert outer.attrs["n"] == 1 and far.attrs["n"] == 1
    assert inner.attrs["n"] == 2 and inner.attrs["bytes"] == 10
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert m.wall == outer.wall
    timing.clear()
    assert timing.records() == []


def test_no_record_or_count_lost_under_thread_contention(rec):
    """More threads than cores and a short switch interval: every span,
    counter and compile-cache event lands once."""
    events = CacheEvents()
    n_threads, n_spans = (os.cpu_count() or 1) + 2, 100

    def work(i):
        for _ in range(n_spans):
            with timing.span("w", rid=i):
                timing.count("n")
                jax.monitoring.record_event(
                    "/jax/compilation_cache/cache_hits")

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    ws = named("w")
    assert len(ws) == n_threads * n_spans == len({r.id for r in ws})
    assert all(r.attrs["n"] == 1 and r.attrs["compile_cache.hit"] == 1
               and r.parent is None for r in ws)
    assert events.counts() == (n_threads * n_spans, 0)


def test_compiles_counted_in_the_span_on_the_compiling_thread(rec):
    CacheEvents()                       # the one listener, registered once
    with timing.span("compile"):
        jax.jit(lambda x: x * 3.0 + 1.0).lower(jnp.ones(7)).compile()
    assert one("compile").attrs.get("backend_compile", 0) >= 1


# ---------------------------------------------------------------------------
# stage steps
# ---------------------------------------------------------------------------

def test_decode_step_spans_and_host_syncs(rec, lm):
    mgr, sm = _sessions(lm)
    timing.clear()
    _, req = mgr.serve({})
    recs = timing.records()
    step = one("step")
    kids = {r.name: r for r in recs if r.parent == step.id}
    assert set(kids) == {"step.input", "step.edge", "step.cloud",
                         "step.commit"}
    order = sorted(kids.values(), key=lambda r: r.start_ns)
    assert [r.name for r in order] == ["step.input", "step.edge",
                                       "step.cloud", "step.commit"]
    # 2 block_until_ready + the (num_slots, 1) token brought back: the
    # logits and boundary checkpoints stay on the device
    assert total(step, "host_sync", recs) == 3
    assert kids["step.commit"].attrs["d2h_bytes"] == 4 * sm.num_slots
    assert kids["step.input"].attrs.get("h2d_bytes", 0) <= 4 * sm.num_slots
    assert kids["step.commit"].attrs["carry_rows"] == len(sm.session_ids())
    pipe = mgr.pool.active
    assert req.t_edge == kids["step.edge"].wall * pipe.edge_scale
    assert req.t_cloud == kids["step.cloud"].wall
    mgr.close()


def test_cnn_frame_spans_and_host_syncs(rec, cnn):
    runner, img = cnn
    mgr = PipelineManager(runner, 2, NetworkModel(20.0), img)
    timing.clear()
    _, req = mgr.serve(img)
    recs = timing.records()
    step = one("step")
    assert {r.name for r in recs if r.parent == step.id} == \
        {"step.edge", "step.cloud"}
    assert total(step, "host_sync", recs) == 2
    assert req.t_edge == one("step.edge").wall * mgr.pool.active.edge_scale
    assert req.t_cloud == one("step.cloud").wall
    mgr.close()


# ---------------------------------------------------------------------------
# repartitions
# ---------------------------------------------------------------------------

def test_switch_b1_spans_through_the_engine(rec, lm):
    cfg, params, inputs = lm
    mgr = PipelineManager(StageRunner(cfg, params), split=1,
                          net=NetworkModel(20.0), sample_inputs=inputs)
    eng = ServingEngine(mgr, clock=VirtualClock())
    eng.schedule_switch(0.6, "switch_b1", 2, bandwidth_mbps=5.0)
    tl = eng.run(request_stream(inputs, fps=4.0, duration=1.5))
    mgr.close()
    recs = timing.records()
    run, sw = one("engine.run"), one("engine.switch")
    assert sw.parent == run.id and sw.attrs["switch"] == 0
    assert sw.attrs["strategy"] == "switch_b1"
    assert (sw.attrs["old"], sw.attrs["new"]) == (1, 2)
    build = [r for r in named("pool.build") if r.attrs.get("switch") == 0]
    assert len(build) == 1 and build[0].parent == sw.id
    build = build[0]
    assert build.attrs["owns_weights"] and build.attrs["cold"]
    weights = [r for r in named("build.weights") if r.parent == build.id]
    assert len(weights) == 1 and weights[0].attrs["bytes"] > 0
    execs = [r for r in named("build.exec")
             if build.id in (r.parent, r.attrs.get("cause"))]
    assert sorted(r.attrs["stage"] for r in execs) == ["cloud", "edge"]
    assert all(r.attrs["switch"] == 0 for r in weights + execs)
    assert eng.reports[0].t_build == build.wall
    assert eng.reports[0].build_detail.t_weights == weights[0].wall
    # every served request is one engine.request with its step inside
    reqs = named("engine.request")
    assert sorted(r.attrs["rid"] for r in reqs) == \
        sorted(r.rid for r in tl.records if r.served)
    ids = {r.id for r in reqs}
    steps = [s for s in named("step") if s.parent in ids]
    assert len(steps) == len(reqs)           # the others: the engine's warm-up
    assert all(s.attrs["rid"] == next(r.attrs["rid"] for r in reqs
                                      if r.id == s.parent) for s in steps)
    events = {k: v for k, v in run.attrs.items()
              if k.startswith("engine.events.")}
    assert events["engine.events.req"] == tl.arrived
    assert events["engine.events.cmd"] == 1


@pytest.mark.parametrize("mode,spans", [
    ("transfer", ["handoff.export", "handoff.import"]),
    ("recompute", ["handoff.recompute"])])
def test_switch_b2_handoff_spans(rec, lm, mode, spans):
    mgr, sm = _sessions(lm, force_mode=mode)
    mgr.serve({})
    timing.clear()
    rep = mgr.repartition("switch_b2", 2)
    recs = timing.records()
    got = [r for r in recs if r.name in ("handoff.export", "handoff.import",
                                         "handoff.recompute")]
    assert [r.name for r in got] == spans
    assert all(r.attrs["layers"] == 1 and r.attrs["mode"] == mode
               for r in got)
    assert rep.handoff_mode == mode
    walls = 0.0
    for r in got:
        walls += r.wall
    h = mgr.pool.handoffs[-1]
    assert h.t_wall == walls
    assert rep.t_handoff == h.t_wall + h.t_network
    assert rep.t_build == one("pool.build").wall
    # an attention model's state is KV alone: one child span per arm
    # part, and the KV bytes counted (serialized, or rebuilt)
    kids = {r.name: r for r in recs if r.parent in {g.id for g in got}}
    if mode == "transfer":
        assert set(kids) == {"handoff.export.kv", "handoff.import.kv"}
        ex = kids["handoff.export.kv"]
        assert ex.attrs["handoff_bytes.kv"] == rep.handoff_bytes > 0
        assert ex.attrs["d2h_bytes"] >= rep.handoff_bytes
        assert kids["handoff.import.kv"].attrs["h2d_bytes"] > 0
    else:
        assert not kids
        assert got[0].attrs["handoff_bytes.kv"] > 0
        assert "handoff_bytes.ssm" not in got[0].attrs
    mgr.close()


def test_admission_and_eviction_spans(rec, lm):
    mgr, sm = _sessions(lm)
    admits = named("sessions.admit")
    assert len(admits) == 2
    for a in admits:
        kids = {r.name for r in timing.records() if r.parent == a.id}
        assert {"admit.prefill", "admit.place"} <= kids
    assert len(named("admit.calibrate")) == 1      # the first admission
    sid = sm.session_ids()[0]
    sm.evict(sid)
    ev = one("sessions.evict")
    park = one("sessions.park")
    assert park.parent == ev.id and park.attrs["sid"] == sid
    assert park.attrs["d2h_bytes"] > 0
    mgr.close()


def test_slot_rows_move_in_one_program_compiled_once(rec, lm):
    """An admission places its slot's rows with one program and one host
    sync; an ended session is parked with one take and one clear and one
    fetch; other slot indices reuse the same compiles."""
    CacheEvents()                       # the listener counting compiles
    mgr, sm = _sessions(lm)
    for place in named("admit.place"):
        assert place.attrs["slot_rows"] == 1
        assert place.attrs.get("host_sync", 0) == 0
        assert place.attrs.get("d2h_bytes", 0) == 0
    sm.evict(sm.session_ids()[0])
    park = one("sessions.park")
    assert park.attrs["slot_rows"] == 2 and park.attrs["host_sync"] == 1
    assert park.attrs["d2h_bytes"] > 0
    rng = np.random.default_rng(1)
    with timing.span("reuse"):
        for _ in range(3):              # a different free slot each time
            sid = sm.session_ids()[0]
            sm.admit(rng.integers(0, lm[0].vocab_size, 6).astype(np.int32))
            sm.evict(sid)
    reuse = one("reuse")
    assert total(reuse, "backend_compile", timing.records()) == 0
    assert total(reuse, "slot_rows", timing.records()) == 3 * (1 + 2)
    mgr.close()


def test_step_commit_compiles_once_across_steps_and_slot_changes(rec, lm):
    """After the first step, decode steps compile nothing: not as the
    live slots change (an admission into a free slot, a session ended),
    and each commit writes exactly the live rows into the carry."""
    CacheEvents()
    mgr, sm = _sessions(lm)
    mgr.serve({})
    rng = np.random.default_rng(2)
    timing.clear()
    mgr.serve({})
    sm.evict(sm.session_ids()[0])
    mgr.serve({})
    sm.admit(rng.integers(0, lm[0].vocab_size, 4).astype(np.int32))
    mgr.serve({})
    recs = timing.records()
    steps = named("step")
    assert len(steps) == 3
    assert sum(total(st, "backend_compile", recs) for st in steps) == 0
    assert [r.attrs["carry_rows"] for r in named("step.commit")] \
        == [2, 1, 2]
    mgr.close()


# ---------------------------------------------------------------------------
# the profiler's clock
# ---------------------------------------------------------------------------

def test_annotations_on_the_profilers_clock(rec, tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with timing.span("probe"):
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    probe = one("probe")
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[-1]
    data = ProfileData.from_file(path)
    start = None
    found = []
    for plane in data.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start = stats["profile_start_time"]
        for line in plane.lines:
            found += [ev for ev in line.events if ev.name == "nk.probe"]
    assert start is not None and len(found) == 1
    assert abs(start + found[0].start_ns - probe.start_ns) < 1e6
