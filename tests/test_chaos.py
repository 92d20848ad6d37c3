"""Chaos grid: {fault plan x strategy} under deterministic fault injection.

Every cell drives a full serving run (request stream on a quantised
``VirtualClock``) through a ``SimPool`` (analytic build pricing, real
``PipelinePool`` control plane) while a seeded ``FaultPlan`` injects one
failure family:

* ``none``        — control cell (no injectors);
* ``build_fail``  — every pipeline build raises (p=1);
* ``build_stall`` — every build wedges until ``plan.release()``: the
  switch watchdog must abort + roll back instead of hanging the stream;
* ``link_outage`` — the cloud link dies for 6 s mid-run: the circuit
  breaker must enter edge-only degraded mode and recover (MTTR);
* ``slow_cloud``  — keyed per-request cloud stragglers.

Each cell runs twice with the same seed, once per module: the two
``ServiceTimeline.serialize()`` strings must match byte for byte (keyed
fault draws + the clock quantum absorbing scheduler jitter), and the
recovery assertions read the first run's metrics.
"""
import functools
import warnings

import pytest

from repro.core.faults import faults
from repro.core.network import BandwidthTrace, CircuitBreaker
from repro.core.switching import PipelineManager
from repro.serving.clock import VirtualClock
from repro.serving.engine import ServingEngine, request_stream
from repro.serving.sim import SimPool, SimRunner

# one quantum absorbs scheduler jitter: a watchdog abort measures
# WATCHDOG_S + fence grace (~0.35 s real) and always charges 2 quanta;
# a fast switch (~ms real) always charges 1
QUANTUM = 0.25
WATCHDOG_S = 0.30
DURATION = 20.0
FPS = 2.0
L = 8                     # SimRunner layers

PLANS = {
    "none": "",
    "build_fail": "build_fail(p=1.0)",
    "build_stall": "build_stall(p=1.0)",
    "link_outage": "link_outage(at=6.0,dur=6.0)",
    "slow_cloud": "slow_cloud(factor=6.0,p=0.3)",
}
STRATS = ("pause_resume", "switch_a", "switch_b2")
SEED = 0

# pre-switch split each strategy must be serving after a watchdog
# rollback under build_stall (switch_a's FIRST switch is a standby swap
# that needs no build, so only its second switch aborts)
ROLLBACK_SPLIT = {"pause_resume": 2, "switch_b2": 2, "switch_a": 6}


def run_cell(spec: str, strat: str, seed: int):
    """One {plan x strategy} serving run; returns (metrics, serialized)."""
    clock = VirtualClock(quantum=QUANTUM)
    runner = SimRunner(L)
    plan = faults(spec, seed=seed)
    trace = plan.apply_to_trace(BandwidthTrace(steps=[(0.0, 20.0)]))
    pool = SimPool(runner, trace.at(0.0), fault_plan=plan,
                   mem_budget_bytes=runner.edge_param_bytes(L) * 2)
    mgr = PipelineManager(runner, 2, trace.at(0.0), None, pool=pool,
                          standby_split=6 if strat == "switch_a" else None)
    pool.sim_clock = clock          # deployment-time builds above were free
    eng = ServingEngine(mgr, clock=clock, switch_timeout_s=WATCHDOG_S,
                        breaker=CircuitBreaker(), fault_plan=plan)
    plan.arm()                      # open the valve only for the stream
    eng.schedule_switch(3.0, strat, 6)
    eng.schedule_switch(15.0, strat, 2)
    for t in trace.change_points():
        eng.schedule_network(t, trace.at(t).bandwidth_mbps)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            tl = eng.run(request_stream({"x": 0}, fps=FPS, duration=DURATION),
                         duration=DURATION)
        blob = tl.serialize()
        s = tl.summary()
        active = pool.snapshot_active()
        drops = {}
        for r in tl.records:
            if r.drop_reason is not None:
                drops[r.drop_reason] = drops.get(r.drop_reason, 0) + 1
        metrics = {
            "downtime_ms": s["downtime_ms"],
            "served": s["served"],
            "dropped": s["dropped"],
            "outage_drops": drops.get("outage", 0),
            "link_down_drops": drops.get("link_down", 0),
            "busy_drops": drops.get("busy", 0),
            "aborted": s["aborted_switches"],
            "full_outage_windows": sum(1 for w in tl.windows
                                       if w.full_outage),
            "closed_degraded_windows": sum(1 for w in tl.degraded
                                           if w.closed),
            "degraded_s": s["degraded_s"],
            "mttr_s": round(tl.mttr() or 0.0, 6),
            "p99_ms": s["p99_ms"],
            "t_end": tl.t_end,
            "final_split": active.split if active is not None else -1,
        }
    finally:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")     # zombie-build failures
            plan.release()          # let stalled build zombies exit
            mgr.close()
    return metrics, blob


@functools.cache
def cell(plan: str, strat: str):
    """Both seeded runs of one cell: (first run's metrics, blob, blob)."""
    m1, blob1 = run_cell(PLANS[plan], strat, SEED)
    _, blob2 = run_cell(PLANS[plan], strat, SEED)
    return m1, blob1, blob2


@pytest.mark.parametrize("strat", STRATS)
@pytest.mark.parametrize("plan", list(PLANS))
def test_same_seed_gives_byte_identical_timeline(plan, strat):
    _, blob1, blob2 = cell(plan, strat)
    assert blob1 == blob2, f"nondeterministic timeline for {plan}|{strat}"


def test_control_cell_keeps_the_papers_downtime_ordering():
    down = {s: cell("none", s)[0]["downtime_ms"] for s in STRATS}
    assert down["pause_resume"] > down["switch_b2"] > down["switch_a"], down


def test_build_fail_switch_a_serves_while_pause_resume_goes_dark():
    bf_a = cell("build_fail", "switch_a")[0]
    assert bf_a["outage_drops"] == 0 and bf_a["served"] > 0, bf_a
    # pause_resume's pause landed before the build died: a full outage
    bf_pr = cell("build_fail", "pause_resume")[0]
    assert bf_pr["outage_drops"] > 0, bf_pr
    assert bf_pr["aborted"] >= 1, bf_pr
    assert bf_pr["full_outage_windows"] >= 1, bf_pr


@pytest.mark.parametrize("strat", STRATS)
def test_build_stall_is_aborted_and_rolled_back(strat):
    c = cell("build_stall", strat)[0]
    assert c["t_end"] >= DURATION, f"build_stall wedged {strat}: {c}"
    assert c["aborted"] >= 1, f"no watchdog abort recorded for {strat}: {c}"
    assert c["final_split"] == ROLLBACK_SPLIT[strat], c


@pytest.mark.parametrize("strat", STRATS)
def test_link_outage_enters_and_leaves_degraded_mode(strat):
    d = cell("link_outage", strat)[0]
    assert d["closed_degraded_windows"] >= 1 and d["mttr_s"] > 0, d
    # the breaker degrades to edge-only instead of dropping to a dead link
    assert d["link_down_drops"] == 0, d
