"""Overlapped switching: BuildExecutor, pending-build registry, drain
semantics, eviction-vs-in-flight safety, and the async strategy paths."""
import threading
import time
import warnings

import jax
import pytest

from repro.configs import get_config
from repro.core import (BackgroundBuildFailed, BuildExecutor, NetworkModel,
                        PipelineManager, PipelinePool, StageRunner)
from repro.core.pipeline import EdgeCloudPipeline
from repro.models import transformer as T


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2.5-3b").reduced()
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    runner = StageRunner(cfg, params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                              cfg.vocab_size)
    return cfg, runner, {"tokens": toks}


def _pool(runner, inputs, **kw):
    return PipelinePool(runner, NetworkModel(20.0), inputs, **kw)


def _param_bytes(runner):
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(runner.params))


# ---------------------------------------------------------------------------
# BuildExecutor
# ---------------------------------------------------------------------------

def test_executor_runs_jobs_off_thread_and_drains():
    ex = BuildExecutor()
    seen = []
    h1 = ex.submit(lambda: seen.append(threading.current_thread().name) or 1)
    h2 = ex.submit(lambda: 2)
    assert ex.drain(timeout=10.0)
    assert h1.done and h2.done
    assert h1.result == 1 and h2.result == 2
    assert seen and seen[0] != threading.main_thread().name
    ex.shutdown()


def test_executor_survives_failing_job():
    """A raising job must not kill the worker; later jobs still run."""
    ex = BuildExecutor()
    bad = ex.submit(lambda: 1 / 0)
    good = ex.submit(lambda: "ok")
    assert ex.drain(timeout=10.0)
    assert bad.failed and isinstance(bad.error, ZeroDivisionError)
    assert good.result == "ok"
    ex.shutdown()


def test_executor_inline_mode_is_synchronous():
    ex = BuildExecutor(inline=True)
    h = ex.submit(lambda: threading.current_thread().name)
    assert h.done and h.result == threading.current_thread().name


def test_handle_done_callback_after_completion_runs_immediately():
    ex = BuildExecutor(inline=True)
    h = ex.submit(lambda: 7)
    got = []
    h.add_done_callback(lambda hh: got.append(hh.result))
    assert got == [7]


# ---------------------------------------------------------------------------
# pool: pending-build registry
# ---------------------------------------------------------------------------

def test_submit_build_coalesces_and_drain_is_deterministic(setup):
    cfg, runner, inputs = setup
    pool = _pool(runner, inputs)
    e, _ = pool.ensure(1)
    pool.activate(e.key)
    h1 = pool.submit_build(2, owns_weights=True, cold=True)
    h2 = pool.submit_build(2, owns_weights=True, cold=True)   # in flight
    assert h1 is h2                     # coalesced, not duplicated
    assert pool.pending(2, True) is h1
    pool.drain()
    assert pool.pending(2, True) is None
    assert pool.has(2, True)


def test_switch_during_inflight_speculation_awaits_not_duplicates(setup):
    """A switch that targets a key whose speculative build is in flight
    must await that build (wait-hit), not build a second pipeline."""
    cfg, runner, inputs = setup
    mgr = PipelineManager(runner, split=0, net=NetworkModel(20.0),
                          sample_inputs=inputs)
    strat = mgr.get_strategy("switch_pool(k=1)")
    strat.switch(mgr.pool, 2)           # miss; speculation for 0 submitted
    assert mgr.pool.pending(0, True) is not None
    rep = strat.switch(mgr.pool, 0)     # target is the in-flight key
    assert rep.cache_hit
    assert "in-flight" in rep.note
    assert mgr.active.split == 0
    mgr.drain()
    out, _ = mgr.serve(inputs)
    assert out.shape[-1] == cfg.vocab_size


def test_eviction_refuses_inflight_builds(setup):
    """evict_to_budget racing a pending build: the in-flight key must
    survive and release() must refuse to reap it."""
    cfg, runner, inputs = setup
    pbytes = _param_bytes(runner)
    pool = _pool(runner, inputs, mem_budget_bytes=int(1.5 * pbytes))
    e, _ = pool.ensure(1)
    pool.activate(e.key)
    pool.ensure(0, owns_weights=True, cold=True, reuse=False)  # 1x charged

    gate = threading.Event()
    real_build = EdgeCloudPipeline.build

    def slow_build(self, *a, **kw):
        gate.wait(timeout=30.0)
        return real_build(self, *a, **kw)

    try:
        EdgeCloudPipeline.build = slow_build
        pool.submit_build(2, owns_weights=True, cold=True)
        with pytest.raises(ValueError, match="in flight"):
            pool.release((2, True))
        evicted = pool.evict_to_budget()        # races the pending build
        assert (2, True) not in evicted
    finally:
        EdgeCloudPipeline.build = real_build
        gate.set()
    pool.drain()
    # the landed build enforced its own keep; budget holds afterwards
    assert pool.has(2, True)
    pool.evict_to_budget()
    assert pool.additional_bytes() <= int(1.5 * pbytes)


def test_failed_background_build_warns_on_drain_and_service_survives(setup):
    cfg, runner, inputs = setup
    mgr = PipelineManager(runner, split=1, net=NetworkModel(20.0),
                          sample_inputs=inputs)
    ref, _ = mgr.serve(inputs)
    real_build = EdgeCloudPipeline.build

    def broken_build(self, *a, **kw):
        raise RuntimeError("backing store gone")

    try:
        EdgeCloudPipeline.build = broken_build
        mgr.pool.submit_build(2, owns_weights=True, cold=True)
        with pytest.warns(BackgroundBuildFailed, match="backing store gone"):
            mgr.drain()
    finally:
        EdgeCloudPipeline.build = real_build
    assert not mgr.pool.has(2, True)
    out, _ = mgr.serve(inputs)          # the active pipeline never blinked
    assert float(jax.numpy.max(jax.numpy.abs(out - ref))) < 1e-4
    # the worker survived: a subsequent build succeeds
    mgr.pool.submit_build(2, owns_weights=True, cold=True)
    mgr.drain()
    assert mgr.pool.has(2, True)


# ---------------------------------------------------------------------------
# pool: ensure() active-replacement leak (regression)
# ---------------------------------------------------------------------------

def test_rebuilding_active_key_closes_orphaned_pipeline(setup):
    """Rebuilding the key that is currently active replaces the dict entry;
    the old object becomes unreachable through the pool and must be closed
    — no ready-but-orphaned pipelines may remain."""
    cfg, runner, inputs = setup
    pool = _pool(runner, inputs)
    e1, _ = pool.ensure(1)
    pool.activate(e1.key)
    old_pipe = e1.pipeline
    e2, hit = pool.ensure(1, reuse=False)       # rebuild the active key
    assert not hit and e2.pipeline is not old_pipe
    assert not old_pipe.ready                   # closed, not leaked
    assert pool.active is e2.pipeline and e2.pipeline.ready
    out, _ = pool.active.process(inputs)
    assert out.shape[-1] == cfg.vocab_size


# ---------------------------------------------------------------------------
# async strategies: the serving thread no longer stalls
# ---------------------------------------------------------------------------

def test_switch_a_returns_after_pointer_swap(setup):
    cfg, runner, inputs = setup
    mgr = PipelineManager(runner, split=1, net=NetworkModel(20.0),
                          sample_inputs=inputs, standby_split=2)
    rep = mgr.repartition("switch_a", 2)
    # blocked time is the pointer swap, not the standby rebuild
    assert rep.t_blocked < 0.05
    assert rep.t_background_wall == 0.0         # not yet landed (async)
    out, _ = mgr.serve(inputs)                  # serving while it builds
    assert out.shape[-1] == cfg.vocab_size
    mgr.drain()
    assert rep.t_background_wall > 0.0          # filled in by the worker
    assert mgr.standby is not None and mgr.standby.ready
    assert mgr.standby.split == 1               # rebuilt for the old config


def test_background_rebuild_never_touches_active_pipeline(setup):
    """Corner: standby built for the serving split. The mismatch switch
    activates it, making the background rebuild target the now-active key —
    the worker must refuse to rebuild (and close) the serving pipeline."""
    from repro.core.strategies import StandbySplitMismatch

    cfg, runner, inputs = setup
    mgr = PipelineManager(runner, split=2, net=NetworkModel(20.0),
                          sample_inputs=inputs, standby_split=2)
    with pytest.warns(StandbySplitMismatch):
        mgr.repartition("switch_a", 0, drain=False)
    active = mgr.active
    mgr.drain()
    assert mgr.active is active and active.ready    # untouched, still serving
    assert mgr.pool.standby_key != mgr.pool.active_key
    out, _ = mgr.serve(inputs)
    assert out.shape[-1] == cfg.vocab_size


def test_switch_a_degrades_to_warm_build_after_failed_rebuild(setup):
    """A failed background standby rebuild must not take switch_a down:
    the next switch falls back to a warm build and re-arms the standby."""
    from repro.core.strategies import StandbySplitMismatch

    cfg, runner, inputs = setup
    mgr = PipelineManager(runner, split=1, net=NetworkModel(20.0),
                          sample_inputs=inputs, standby_split=2)
    real_build = EdgeCloudPipeline.build

    def broken_build(self, *a, **kw):
        raise RuntimeError("edge node out of memory")

    try:
        EdgeCloudPipeline.build = broken_build
        mgr.repartition("switch_a", 2, drain=False)  # swap ok; rebuild dies
        with pytest.warns(BackgroundBuildFailed, match="out of memory"):
            mgr.drain()
    finally:
        EdgeCloudPipeline.build = real_build
    assert mgr.standby is None
    with pytest.warns(StandbySplitMismatch, match="fell back"):
        rep = mgr.repartition("switch_a", 1)         # degraded, not dead
    assert mgr.active.split == 1 and not rep.full_outage
    mgr.drain()
    assert mgr.standby is not None and mgr.standby.ready  # Scenario A restored
    out, _ = mgr.serve(inputs)
    assert out.shape[-1] == cfg.vocab_size


def test_switch_pool_speculation_is_background(setup):
    cfg, runner, inputs = setup
    mgr = PipelineManager(runner, split=1, net=NetworkModel(20.0),
                          sample_inputs=inputs)
    rep = mgr.repartition("switch_pool(k=1)", 2)
    mgr.drain()
    # speculation cost landed on the report, off the serving thread: the
    # switch blocked for (at most) a warm build while the worker spent a
    # full cold owned-weights build
    assert rep.t_background_wall > 0.0
    assert rep.t_blocked < rep.t_background_wall
    assert mgr.pool.has(1, True)                # predicted split pre-built
