"""Slot-indexed multi-session decode pools (``repro.serving.sessions``).

The load-bearing invariants:

* row independence — admission into a masked slot NEVER perturbs a live
  slot's logits (bit-identical vs a pool that never admitted);
* eviction/readmission round-trips a session's state bit-exactly through
  the serialized hand-off representation;
* a whole-batch repartition hand-off (transfer AND recompute arms) is
  bit-identical per slot against a no-switch control, with zero dropped
  sessions;
* a slot-count-1 pool reproduces the single-session ``DecodeSession``
  trajectory (tokens exactly, logits to float32 rounding).
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (NetworkModel, make_stateful_manager,
                        per_layer_state_bytes)
from repro.core.stateful import StatefulStageRunner, is_kv
from repro.models import transformer as T
from repro.serving import (ServingEngine, SlotPoolFull, VirtualClock,
                           make_session_manager, request_stream)
from repro.serving.sessions import SessionManager


def _cfg(name="qwen2.5-3b", num_layers=2):
    return dataclasses.replace(get_config(name).reduced(),
                               num_layers=num_layers)


def _ragged(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)
            for n in lens]


@pytest.fixture(scope="module")
def tf_runner():
    cfg = _cfg()
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    return StatefulStageRunner(cfg, params, max_seq=32)


# ---------------------------------------------------------------------------
# slot isolation / admission
# ---------------------------------------------------------------------------

def test_midflight_admission_never_perturbs_live_slots(tf_runner):
    cfg = tf_runner.cfg
    pa, pb = _ragged(cfg, (5, 9))
    solo = SessionManager(tf_runner, num_slots=4)
    a = solo.admit(pa)
    for _ in range(2):
        solo.decode_step()
    solo_mid = solo.logits_for(a)
    for _ in range(2):
        solo.decode_step()
    solo_final, solo_toks = solo.logits_for(a), solo.tokens_for(a)

    sm = SessionManager(tf_runner, num_slots=4)
    a2 = sm.admit(pa)
    for _ in range(2):
        sm.decode_step()
    np.testing.assert_array_equal(sm.logits_for(a2), solo_mid)
    b = sm.admit(pb)                 # mid-flight, into a masked dead slot
    for _ in range(2):
        sm.decode_step()
    np.testing.assert_array_equal(sm.logits_for(a2), solo_final)
    np.testing.assert_array_equal(sm.tokens_for(a2), solo_toks)
    assert sm.slot_info(b).pos == len(pb) + 2


def test_evict_readmit_round_trips_state(tf_runner):
    cfg = tf_runner.cfg
    pa, pb, pc = _ragged(cfg, (6, 4, 3), seed=1)
    sm = SessionManager(tf_runner, num_slots=3)
    a, b = sm.admit(pa), sm.admit(pb)
    sm.decode_step()
    before_logits, before_toks = sm.logits_for(a), sm.tokens_for(a)
    sm.evict(a)
    assert a in sm.parked_ids() and sm.session_ids() == [b]
    sm.admit(pc)                     # pool keeps serving while a is parked
    sm.decode_step()
    sm.readmit(a)
    np.testing.assert_array_equal(sm.logits_for(a), before_logits)
    np.testing.assert_array_equal(sm.tokens_for(a), before_toks)
    sm.decode_step()                 # restored state still decodes
    assert sm.slot_info(a).pos == before_toks.shape[0] + 1


def test_evict_readmit_round_trips_hybrid_state():
    """A conv/SSM + KV pool: the parked payload is byte for byte the
    slot's rows (KV sliced to its prefix), every entry's row is zero
    after the eviction, and readmission restores logits and tokens
    bit-exactly."""
    cfg = _cfg("zamba2-7b", num_layers=4)
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    runner = StatefulStageRunner(cfg, params, max_seq=32)
    pa, pb, pc = _ragged(cfg, (6, 4, 3), seed=2)
    sm = SessionManager(runner, num_slots=3)
    b, a = sm.admit(pb), sm.admit(pa)        # a in a slot other than 0
    sm.decode_step()
    assert {k[:2] for k in sm.cache} >= {"co", "ss", "ak", "av"}
    j, pos = sm.slot_info(a).index, sm.slot_info(a).pos
    assert j == 1
    expect = {}
    for k, v in sm.cache.items():
        row = np.asarray(v)[j]
        expect[k] = row[:, :pos] if is_kv(k) else row
    before_logits, before_toks = sm.logits_for(a), sm.tokens_for(a)
    sm.evict(a)
    parked = sm._parked[a]["state"]
    assert set(parked) == set(expect)
    for k, row in expect.items():
        dtype, shape, buf = parked[k]
        assert (dtype, tuple(shape)) == (str(row.dtype), row.shape), k
        assert buf == row.tobytes(), k
        assert not np.asarray(sm.cache[k])[j].any(), k
    sm.admit(pc)
    sm.decode_step()
    sm.readmit(a)
    np.testing.assert_array_equal(sm.logits_for(a), before_logits)
    np.testing.assert_array_equal(sm.tokens_for(a), before_toks)
    sm.decode_step()
    assert sm.slot_info(a).pos == before_toks.shape[0] + 1


def test_preemption_parks_lru_and_full_pool_raises(tf_runner):
    cfg = tf_runner.cfg
    pa, pb, pc = _ragged(cfg, (4, 5, 6), seed=3)
    strict = SessionManager(tf_runner, num_slots=2, allow_preempt=False)
    strict.admit(pa), strict.admit(pb)
    with pytest.raises(SlotPoolFull):
        strict.admit(pc)

    sm = SessionManager(tf_runner, num_slots=2)
    a, b = sm.admit(pa), sm.admit(pb)
    c = sm.admit(pc)                 # preempts the LRU live slot (a)
    assert sm.parked_ids() == [a]
    assert set(sm.session_ids()) == {b, c}


def test_memory_budget_evicts_lru_on_admission(tf_runner):
    cfg = tf_runner.cfg
    per = per_layer_state_bytes(cfg, seq_len=8, batch=1, act_bytes=4) \
        * len(tf_runner.units)
    sm = SessionManager(tf_runner, num_slots=4,
                        mem_budget_bytes=int(2.5 * per))
    pa, pb, pc = _ragged(cfg, (8, 8, 8), seed=4)
    a, b = sm.admit(pa), sm.admit(pb)
    assert sm.state_bytes() <= 2.5 * per
    c = sm.admit(pc)                 # third live slot busts the budget
    assert a in sm.parked_ids()
    assert set(sm.session_ids()) == {b, c}
    assert sm.state_bytes() <= 2.5 * per


def test_moe_family_rejected():
    cfg = _cfg("mixtral-8x22b")
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    runner = StatefulStageRunner(cfg, params, max_seq=32)
    with pytest.raises(ValueError, match="MoE"):
        SessionManager(runner, num_slots=2)


# ---------------------------------------------------------------------------
# slot-count-1 parity with the single-session regime
# ---------------------------------------------------------------------------

def test_slot_count_one_matches_decode_session():
    cfg = _cfg()
    net = NetworkModel(1000.0)
    mgr1, session = make_stateful_manager(cfg, split=1, net=net,
                                          prompt_len=8, max_seq=32, seed=0)
    for _ in range(3):
        mgr1.active.process()
    mgrp, sm = make_session_manager(cfg, split=1, net=net, num_slots=1,
                                    max_seq=32, seed=0)
    # the exact seeded prompt make_stateful_manager prefilled
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                           cfg.vocab_size))[0]
    sid = sm.admit(prompt)
    for _ in range(3):
        mgrp.active.process()
    # two compiled programs, not one: the pool admits through the masked
    # fixed (1, max_seq) prefill and decodes with a (1,) position vector,
    # the session prefills at the exact prompt length.  Their float sums
    # are ordered differently (observed: 2.4e-7 on logits of order 1), so
    # logits agree to float32 rounding and the greedy tokens exactly
    np.testing.assert_allclose(sm.logits_for(sid),
                               np.asarray(session.last_logits)[0],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(sm.tokens_for(sid),
                                  np.asarray(session.tokens)[0])
    mgr1.close()
    mgrp.close()


# ---------------------------------------------------------------------------
# whole-batch hand-off under repartition
# ---------------------------------------------------------------------------

def _eight_session_pool(arch, force_mode):
    cfg = _cfg(arch)
    nl = cfg.num_layers
    mgr, sm = make_session_manager(cfg, split=nl, net=NetworkModel(1000.0),
                                   num_slots=8, max_seq=32, seed=0,
                                   force_mode=force_mode)
    sids = [sm.admit(p) for p in _ragged(cfg, range(3, 11), seed=7)]
    for _ in range(2):
        mgr.active.process()
    snap = sm.snapshot()
    for _ in range(2):               # control arm: no switch
        mgr.active.process()
    control = {s: (sm.logits_for(s), sm.tokens_for(s)) for s in sids}
    sm.restore(snap)
    return mgr, sm, sids, snap, control


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "falcon-mamba-7b"])
def test_batch_transfer_bit_identical_eight_ragged_sessions(arch):
    """Transfer arm: >= 8 concurrent ragged-context sessions survive a
    mid-stream repartition (away AND back) with zero drops and per-slot
    bit-identical logits/tokens vs a no-switch control.  Switching back
    before resuming keeps the decode program identical to the control's,
    so any drift whatsoever would be the hand-off's fault — and the
    hand-off is byte-exact, twice."""
    nl = _cfg(arch).num_layers
    mgr, sm, sids, snap, control = _eight_session_pool(arch, "transfer")
    mgr.repartition("switch_b2", 1)          # moves layers [1, nl)
    assert mgr.pool.handoffs[-1].mode == "transfer"
    for k, v in snap["cache"].items():       # the hand-off itself is exact
        np.testing.assert_array_equal(np.asarray(sm.cache[k]),
                                      np.asarray(v), err_msg=str(k))
    mgr.repartition("switch_b2", nl)         # and back
    assert mgr.pool.handoffs[-1].mode == "transfer"
    assert not any(h.fallback for h in mgr.pool.handoffs)
    for _ in range(2):
        mgr.active.process()
    assert set(sm.session_ids()) == set(sids)    # zero dropped
    for s in sids:
        logits, toks = control[s]
        np.testing.assert_array_equal(sm.logits_for(s), logits, err_msg=s)
        np.testing.assert_array_equal(sm.tokens_for(s), toks, err_msg=s)
    mgr.close()


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "falcon-mamba-7b"])
def test_batch_recompute_preserves_eight_ragged_sessions(arch):
    """Recompute arm: the masked fixed-shape rebuild with a per-slot
    length vector restores every slot within float tolerance (same
    contract as the single-session recompute test), every slot's greedy
    trajectory survives the switch exactly, and nothing is dropped.
    (Cross-split logits are compared with allclose, not array_equal: XLA
    fuses the SSM scan differently per stage boundary, a ~1e-10 state
    rounding outside the hand-off's control.)"""
    nl = _cfg(arch).num_layers
    mgr, sm, sids, snap, control = _eight_session_pool(arch, "recompute")
    tok_before = np.asarray(sm.next_token())
    mgr.repartition("switch_b2", 1)          # moves layers [1, nl)
    h = mgr.pool.handoffs[-1]
    assert h.mode == "recompute" and not h.fallback
    for k, v in snap["cache"].items():
        np.testing.assert_allclose(np.asarray(sm.cache[k]), np.asarray(v),
                                   atol=1e-4, err_msg=str(k))
    np.testing.assert_array_equal(np.asarray(sm.next_token()), tok_before)
    for _ in range(2):
        mgr.active.process()
    assert set(sm.session_ids()) == set(sids)    # zero dropped
    for s in sids:
        logits, toks = control[s]
        np.testing.assert_array_equal(sm.tokens_for(s), toks, err_msg=s)
        np.testing.assert_allclose(sm.logits_for(s), logits, atol=1e-4,
                                   err_msg=s)
    mgr.close()


# ---------------------------------------------------------------------------
# engine integration: scheduled admission + per-session attribution
# ---------------------------------------------------------------------------

def test_engine_scheduled_admission_and_session_attribution():
    cfg = _cfg()
    mgr, sm = make_session_manager(cfg, split=1, net=NetworkModel(1000.0),
                                   num_slots=2, max_seq=32, seed=0)
    first, mid = _ragged(cfg, (6, 4), seed=9)
    sm.admit(first, sid="first")
    eng = ServingEngine(mgr, clock=VirtualClock())
    eng.schedule_admit(1.0, mid, sid="mid")
    tl = eng.run(request_stream({}, fps=2.0, duration=2.0))
    assert set(sm.session_ids()) == {"first", "mid"}
    summary = tl.session_summary()
    assert summary["first"]["served"] >= 1
    early = [r for r in tl.records if r.served and r.t_arrival < 1.0]
    assert early and all(r.sessions == ("first",) for r in early)
    late = [r for r in tl.records if r.served and r.t_arrival >= 1.0]
    assert late and all(set(r.sessions) == {"first", "mid"} for r in late)
    mgr.close()
