"""Fault injection + failure-hardened switching.

Covers the chaos subsystem end to end at unit scale: seeded injector
determinism, retry backoff properties, the distinct build-callback
failure category, the dead-link guard and outage->recovery monitoring,
the circuit breaker, watchdog abort + rollback, edge-only degraded
mode, and the hand-off integrity envelope (detection, stale-epoch
rejection, recompute fallback) on a real tiny model.
"""
import dataclasses
import math
import warnings

import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:
    from _hypothesis_compat import hypothesis, st

from repro.configs import get_config
from repro.core import (BackgroundBuildFailed, BandwidthTrace,
                        BuildCallbackFailed, BuildExecutor, CircuitBreaker,
                        HandoffCorrupted, InjectedBuildFailure, NetworkModel,
                        NetworkMonitor, PipelineManager, RetryPolicy,
                        SwitchAbortedWarning, faults, make_stateful_manager,
                        payload_checksum)
from repro.core.executor import BuildHandle
from repro.core.faults import (BuildFail, LinkOutage, SlowCloud,
                               _keyed_uniform)
from repro.core.stateful import HANDOFF_META_KEY, HandoffIntegrityWarning
from repro.serving import ServingEngine, VirtualClock, request_stream
from repro.serving.sim import SimPool, SimRunner


# ---------------------------------------------------------------------------
# network guards: dead link, outage -> recovery flap
# ---------------------------------------------------------------------------

def test_dead_link_prices_as_inf_not_crash():
    assert NetworkModel(0.0).transfer_time(1000) == math.inf
    assert NetworkModel(-3.0).transfer_time(1) == math.inf
    assert math.isfinite(NetworkModel(20.0).transfer_time(1000))


def test_monitor_survives_outage_then_recovery_flap():
    """A trace step to 0 Mbps and back must read as two detected changes,
    not a ZeroDivisionError on the relative-change test."""
    trace = BandwidthTrace(steps=[(0.0, 20.0), (2.0, 0.0), (4.0, 20.0)])
    mon = NetworkMonitor(trace)
    assert mon.poll(0.0) is None            # first sample primes
    assert mon.poll(1.0) is None
    outage = mon.poll(2.5)
    assert outage is not None and outage.bandwidth_mbps == 0.0
    assert mon.poll(3.0) is None            # still dark: no new change
    recovery = mon.poll(4.5)                # rel change from 0 is infinite
    assert recovery is not None and recovery.bandwidth_mbps == 20.0
    assert mon.poll(5.0) is None


def test_circuit_breaker_is_edge_triggered():
    br = CircuitBreaker(open_after=2, close_after=1)
    assert br.record(0.0, 0.0) is None      # one bad sample: not yet
    assert br.record(1.0, 0.0) == "open"
    assert br.is_open and br.opened_at == 1.0
    assert br.record(2.0, 0.0) is None      # already open: no re-edge
    assert br.record(3.0, 20.0) == "close"
    assert not br.is_open
    assert br.record(4.0, 20.0) is None


# ---------------------------------------------------------------------------
# retry policy: backoff properties (hypothesis)
# ---------------------------------------------------------------------------

@hypothesis.given(st.integers(0, 10_000), st.floats(0.001, 0.2),
                  st.floats(1.5, 3.0), st.floats(0.05, 1.0),
                  st.floats(0.0, 0.5))
@hypothesis.settings(deadline=None, max_examples=30)
def test_backoff_monotone_capped_seed_identical(seed, base, factor, cap,
                                                jitter):
    p = RetryPolicy(max_attempts=7, base_s=base, factor=factor, cap_s=cap,
                    jitter=jitter, seed=seed)
    sched = p.schedule()
    assert len(sched) == 6
    assert all(0.0 <= d <= cap + 1e-12 for d in sched)
    # factor >= 1 + jitter makes the pre-cap schedule monotone, and
    # min(cap, .) preserves that
    assert all(a <= b + 1e-12 for a, b in zip(sched, sched[1:]))
    twin = RetryPolicy(max_attempts=7, base_s=base, factor=factor,
                       cap_s=cap, jitter=jitter, seed=seed)
    assert twin.schedule() == sched          # keyed jitter: byte-identical


def test_retry_policy_rejects_non_monotone_params():
    with pytest.raises(ValueError):
        RetryPolicy(factor=1.0, jitter=0.5)  # jittered draw could shrink
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(base_s=-0.1)


# ---------------------------------------------------------------------------
# build handle: retries, deadline, callback failure category
# ---------------------------------------------------------------------------

def _flaky(fail_times):
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        if calls["n"] <= fail_times:
            raise RuntimeError(f"transient #{calls['n']}")
        return "built"
    return fn


def test_retry_redeems_transient_build_failure():
    h = BuildHandle(_flaky(2), retry=RetryPolicy(max_attempts=3,
                                                 base_s=0.001, cap_s=0.01))
    h._run()
    assert h.attempts == 3
    assert h.error is None and h.result == "built"


def test_retry_exhaustion_surfaces_last_error():
    h = BuildHandle(_flaky(10), retry=RetryPolicy(max_attempts=2,
                                                  base_s=0.001, cap_s=0.01))
    h._run()
    assert h.attempts == 2
    assert h.failed and "transient #2" in str(h.error)


def test_retry_deadline_abandons_early():
    # backoff of ~10 s would land far past the 1 ms deadline: one attempt
    h = BuildHandle(_flaky(10), retry=RetryPolicy(
        max_attempts=5, base_s=10.0, cap_s=10.0, deadline_s=0.001))
    h._run()
    assert h.attempts == 1 and h.failed


def test_callback_failure_is_a_distinct_category():
    assert not issubclass(BuildCallbackFailed, BackgroundBuildFailed)

    def bad_cb(handle):
        raise RuntimeError("boom in callback")

    h = BuildHandle(lambda: 42)
    h.add_done_callback(bad_cb)
    with pytest.warns(BuildCallbackFailed):
        h._run()
    # the BUILD succeeded; only the callback failed
    assert h.error is None and h.result == 42 and h.done


def test_executor_stamps_default_retry_policy():
    ex = BuildExecutor(inline=True,
                       retry=RetryPolicy(max_attempts=3, base_s=0.001,
                                         cap_s=0.01))
    h = ex.submit(_flaky(1))
    assert h.attempts == 2 and h.result == "built"
    ex.shutdown()


# ---------------------------------------------------------------------------
# fault plans: spec parsing, keyed determinism, arming
# ---------------------------------------------------------------------------

def test_faults_spec_parsing_and_registry():
    plan = faults("build_fail(p=0.3)+link_outage(at=1,dur=2)"
                  "+slow_cloud(factor=2.0)", seed=7)
    assert [type(i) for i in plan.injectors] == [BuildFail, LinkOutage,
                                                 SlowCloud]
    assert [i.index for i in plan.injectors] == [0, 1, 2]
    assert all(i.plan is plan for i in plan.injectors)
    assert faults("").injectors == ()        # inert control plan
    with pytest.raises((KeyError, ValueError)):
        faults("no_such_fault(p=1)")
    with pytest.raises(ValueError):
        faults("handoff_corrupt(mode='sideways')")


def test_keyed_draws_are_site_stable():
    assert _keyed_uniform(3, 1, "build", (2, True), 1) == \
        _keyed_uniform(3, 1, "build", (2, True), 1)
    assert _keyed_uniform(3, 1, "build", (2, True), 1) != \
        _keyed_uniform(3, 2, "build", (2, True), 1)
    a = faults("build_fail(p=0.5)", seed=11)
    b = faults("build_fail(p=0.5)", seed=11)
    hits = [a.injectors[0]._hit(("k", False), n) for n in range(32)]
    assert hits == [b.injectors[0]._hit(("k", False), n) for n in range(32)]
    c = faults("build_fail(p=0.5)", seed=12)
    assert hits != [c.injectors[0]._hit(("k", False), n) for n in range(32)]


def test_plan_inert_until_armed():
    plan = faults("build_fail(p=1.0)")
    plan.on_build(("x", False))              # unarmed: no-op, not counted
    assert plan.build_attempts(("x", False)) == 0
    plan.arm()
    with pytest.raises(InjectedBuildFailure):
        plan.on_build(("x", False))
    assert plan.build_attempts(("x", False)) == 1
    assert any("build_fail" in e for e in plan.event_log())
    plan.disarm()
    plan.on_build(("x", False))              # valve closed again
    assert plan.build_attempts(("x", False)) == 1


def test_link_outage_overlays_trace():
    plan = faults("link_outage(at=2.0,dur=2.0)")
    trace = plan.apply_to_trace(BandwidthTrace(steps=[(0.0, 20.0)]))
    assert trace.at(1.0).bandwidth_mbps == 20.0
    assert trace.at(2.0).bandwidth_mbps == 0.0
    assert trace.at(3.9).bandwidth_mbps == 0.0
    assert trace.at(4.0).bandwidth_mbps == 20.0
    assert set(trace.change_points()) == {2.0, 4.0}


def _fake_payload():
    arr = np.arange(8, dtype=np.float32)
    payload = {"layer0": (str(arr.dtype), arr.shape, arr.tobytes())}
    payload[HANDOFF_META_KEY] = (0, 8, payload_checksum(payload))
    return payload


@pytest.mark.parametrize("mode", ["flip", "truncate"])
def test_handoff_corrupt_breaks_checksum_not_envelope(mode):
    payload = _fake_payload()
    crc_before = payload[HANDOFF_META_KEY][2]
    plan = faults(f"handoff_corrupt(p=1.0,mode='{mode}')").arm()
    plan.mutate_handoff(payload, epoch=0)
    # the envelope survives intact (else the mismatch could not be
    # DETECTED), while the tensor bytes no longer match it
    assert payload[HANDOFF_META_KEY][2] == crc_before
    assert payload_checksum(payload) != crc_before
    buf = payload["layer0"][2]
    assert len(buf) == (16 if mode == "truncate" else 32)


# ---------------------------------------------------------------------------
# engine-level hardening (SimPool: real control plane, analytic pricing)
# ---------------------------------------------------------------------------

def _sim_engine(plan, *, split=2, standby_split=None, timeout=0.3,
                breaker=None, mem_mult=2.0, executor=None):
    runner = SimRunner(4)
    net = NetworkModel(20.0)
    budget = int(runner.edge_param_bytes(runner.max_split) * mem_mult)
    pool = SimPool(runner, net, fault_plan=plan, mem_budget_bytes=budget,
                   executor=executor)
    mgr = PipelineManager(runner, split, net, None, pool=pool,
                          standby_split=standby_split)
    clock = VirtualClock(quantum=0.25)
    pool.sim_clock = clock
    eng = ServingEngine(mgr, clock=clock, switch_timeout_s=timeout,
                        breaker=breaker, fault_plan=plan)
    return mgr, pool, eng


def _teardown(plan, mgr):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        plan.release()
        mgr.close()


def test_transient_build_failure_never_drops_a_request():
    """Regression: a build that fails once and then succeeds on retry must
    be invisible to the stream under switch_a — zero drops, zero aborts,
    the one injected failure redeemed on attempt 2."""
    plan = faults("build_fail(times=1)")
    ex = BuildExecutor(retry=RetryPolicy(max_attempts=3, base_s=0.01,
                                         cap_s=0.05))
    mgr, pool, eng = _sim_engine(plan, split=2, standby_split=3, timeout=1.0,
                                 executor=ex)
    plan.arm()
    eng.schedule_switch(1.0, "switch_a", 3)
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            tl = eng.run(request_stream({"x": 0}, fps=2.0, duration=4.0),
                         duration=4.0)
        assert not any(issubclass(w.category, BackgroundBuildFailed)
                       for w in caught), "retry did not redeem the failure"
        assert tl.dropped_count == 0
        assert tl.summary()["aborted_switches"] == 0
        assert tl.served_count > 0
        assert any("build_fail" in e for e in plan.event_log())
        # the standby rebuild hit the injected failure once, retried once
        assert plan.build_attempts((2, True)) == 2
    finally:
        _teardown(plan, mgr)


def test_watchdog_aborts_and_rolls_back_stalled_switch():
    plan = faults("build_stall(p=1.0)")
    mgr, pool, eng = _sim_engine(plan, split=1, timeout=0.2)
    plan.arm()
    eng.schedule_switch(1.0, "switch_b2", 3)
    try:
        with pytest.warns(SwitchAbortedWarning):
            tl = eng.run(request_stream({"x": 0}, fps=2.0, duration=3.0),
                         duration=3.0)
        assert len(tl.windows) == 1 and tl.windows[0].aborted
        active = pool.snapshot_active()
        assert active is not None and active.split == 1   # rolled back
        assert tl.served_count > 0 and tl.t_end >= 3.0    # never wedged
        assert eng.reports[0].aborted
    finally:
        _teardown(plan, mgr)


def test_degraded_mode_enters_and_recovers():
    plan = faults("")
    mgr, pool, eng = _sim_engine(plan, split=1,
                                 breaker=CircuitBreaker())
    eng.schedule_network(2.0, 0.0)           # outage
    eng.schedule_network(5.0, 20.0)          # recovery
    try:
        tl = eng.run(request_stream({"x": 0}, fps=2.0, duration=8.0),
                     duration=8.0)
        assert len(tl.degraded) == 1
        w = tl.degraded[0]
        assert w.closed and w.duration > 0
        assert tl.mttr() and tl.mttr() > 0
        assert any(r.degraded for r in tl.records if r.served)
        assert not any(r.drop_reason == "link_down" for r in tl.records)
        assert not eng.in_degraded
        active = pool.snapshot_active()
        assert active is not None and active.split == 1   # restored
    finally:
        _teardown(plan, mgr)


def test_pick_degraded_split_respects_memory_budget():
    runner = SimRunner(4)
    net = NetworkModel(20.0)
    plan = faults("")
    # budget fits the embedding + 2 layers: deepest edge-only split is 2
    pool = SimPool(runner, net,
                   mem_budget_bytes=runner.edge_param_bytes(2))
    mgr = PipelineManager(runner, 1, net, None, pool=pool)
    eng = ServingEngine(mgr, clock=VirtualClock(), breaker=CircuitBreaker())
    assert eng._pick_degraded_split() == 2
    mgr.close()
    # no budget: the whole model moves to the edge
    pool2 = SimPool(runner, net)
    mgr2 = PipelineManager(runner, 1, net, None, pool=pool2)
    eng2 = ServingEngine(mgr2, clock=VirtualClock(),
                         breaker=CircuitBreaker())
    assert eng2._pick_degraded_split() == runner.max_split
    mgr2.close()
    del plan


# ---------------------------------------------------------------------------
# hand-off integrity on a real (tiny) stateful model
# ---------------------------------------------------------------------------

def _tiny_stateful(**kw):
    cfg = dataclasses.replace(get_config("qwen2.5-3b").reduced(),
                              num_layers=2)
    return make_stateful_manager(cfg, split=1, net=NetworkModel(1000.0),
                                 prompt_len=8, max_seq=64, seed=0, **kw)


def test_corrupted_and_stale_payloads_rejected_state_untouched():
    mgr, session = _tiny_stateful()
    mgr.active.process()
    before = {k: np.asarray(v).copy() for k, v in session.cache.items()}

    # bit flip in one tensor: checksum mismatch, nothing committed
    payload, _ = session.export_layers(0, 2)
    victim = next(k for k in payload if k != HANDOFF_META_KEY)
    dtype, shape, buf = payload[victim]
    b = bytearray(buf)
    b[0] ^= 0xFF
    payload[victim] = (dtype, shape, bytes(b))
    with pytest.raises(HandoffCorrupted, match="checksum"):
        session.import_layers(payload)
    for k, v in session.cache.items():
        np.testing.assert_array_equal(np.asarray(v), before[k], err_msg=k)

    # stale epoch: envelope from another point in time is refused
    stale, _ = session.export_layers(0, 2)
    epoch, pos, crc = stale[HANDOFF_META_KEY]
    stale[HANDOFF_META_KEY] = (epoch + 1, pos, crc)
    with pytest.raises(HandoffCorrupted, match="stale"):
        session.import_layers(stale)

    # an intact payload still round-trips after the rejections
    clean, _ = session.export_layers(0, 2)
    session.import_layers(clean)
    mgr.close()


def test_corrupt_handoff_falls_back_to_recompute():
    """A corrupted transfer heals through masked recompute, and the
    served logits are bit-identical to a clean recompute run."""
    logits = {}
    for mode in ("recompute", "transfer"):
        mgr, session = _tiny_stateful(force_mode=mode)
        mgr.active.process()
        if mode == "transfer":
            mgr.pool.fault_plan = faults("handoff_corrupt(p=1.0)").arm()
            with pytest.warns(HandoffIntegrityWarning):
                mgr.repartition("switch_b2", 2)
            assert mgr.pool.handoffs[-1].fallback
        else:
            mgr.repartition("switch_b2", 2)
        assert mgr.pool.handoffs[-1].mode == "recompute"
        out, _ = mgr.active.process()        # recovered state still decodes
        logits[mode] = np.asarray(out)
        mgr.close()
    assert np.isfinite(logits["transfer"]).all()
    assert np.array_equal(logits["transfer"], logits["recompute"])


def test_corrupt_batch_handoff_falls_back_to_per_slot_recompute():
    """A corrupted whole-batch hand-off (slot pool, several ragged
    sessions in flight) is detected by the integrity envelope and every
    slot is rebuilt by the masked fixed-shape recompute — bit-identical
    per slot to a pool that never switched."""
    from repro.serving import make_session_manager
    cfg = dataclasses.replace(get_config("qwen2.5-3b").reduced(),
                              num_layers=2)
    mgr, sm = make_session_manager(cfg, split=2, net=NetworkModel(1000.0),
                                   num_slots=3, max_seq=32, seed=0,
                                   force_mode="transfer")
    rng = np.random.default_rng(11)
    sids = [sm.admit(rng.integers(0, cfg.vocab_size,
                                  size=n).astype(np.int32))
            for n in (4, 7, 5)]
    mgr.active.process()
    snap = sm.snapshot()
    mgr.active.process()                     # control arm: no switch
    control = {s: (sm.logits_for(s), sm.tokens_for(s)) for s in sids}
    sm.restore(snap)

    mgr.pool.fault_plan = faults("handoff_corrupt(p=1.0)").arm()
    with pytest.warns(HandoffIntegrityWarning):
        mgr.repartition("switch_b2", 1)
    h = mgr.pool.handoffs[-1]
    assert h.fallback and h.mode == "recompute"
    mgr.active.process()
    assert set(sm.session_ids()) == set(sids)    # zero dropped
    for s in sids:
        logits, toks = control[s]
        np.testing.assert_array_equal(sm.logits_for(s), logits, err_msg=s)
        np.testing.assert_array_equal(sm.tokens_for(s), toks, err_msg=s)
    mgr.close()
