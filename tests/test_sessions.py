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
# the step carry on the device: bit-identical to host bookkeeping
# ---------------------------------------------------------------------------

class _HostCarry:
    """The step carry as host numpy bookkeeping: each live row's
    checkpoints, logits and token written one by one, the next token the
    argmax of the host logits, a parked row saved and zeroed.  Replayed
    beside a pool on the same stage outputs, it is the path the device
    carry replaced."""

    def __init__(self, sm):
        self.bounds = np.zeros(sm.carry["bounds"].shape,
                               sm.carry["bounds"].dtype)  # (B, U, S, D)
        self.logits = np.zeros(sm.carry["logits"].shape, np.float32)
        self.tokens = np.zeros((sm.num_slots, sm.max_seq), np.int32)
        self.parked = {}

    def admit(self, sm, sid, prompt):
        j, L = sm.slot_info(sid).index, len(prompt)
        row = np.zeros((1, sm.max_seq), np.int32)
        row[0, :L] = prompt
        r = sm.runner
        logits, _, bounds = r.admit_fn()(r.params, row, np.int32(L))
        self.bounds[j] = np.asarray(bounds)[:, 0]
        self.logits[j] = np.asarray(logits)[0]
        self.tokens[j] = row[0]

    def commit(self, sm, token, bounds, logits):
        """Called with the pool's pre-step positions."""
        tok = np.asarray(token)
        np.testing.assert_array_equal(
            tok, np.argmax(self.logits, -1)[:, None])   # the next token
        b, lg = np.asarray(bounds), np.asarray(logits)
        for sid in sm.session_ids():
            j, pos = sm.slot_info(sid).index, sm.slot_info(sid).pos
            self.tokens[j, pos] = tok[j, 0]
            self.bounds[j, :, pos] = b[:, j, 0]
            self.logits[j] = lg[j]

    def park(self, j, sid):
        self.parked[sid] = (self.bounds[j].copy(), self.logits[j].copy(),
                            self.tokens[j].copy())
        self.bounds[j], self.logits[j], self.tokens[j] = 0, 0, 0

    def readmit(self, j, sid):
        self.bounds[j], self.logits[j], self.tokens[j] = \
            self.parked.pop(sid)

    def check(self, sm):
        np.testing.assert_array_equal(np.asarray(sm.carry["bounds"]),
                                      self.bounds)
        np.testing.assert_array_equal(np.asarray(sm.carry["logits"]),
                                      self.logits)
        for sid in sm.session_ids():
            j, pos = sm.slot_info(sid).index, sm.slot_info(sid).pos
            np.testing.assert_array_equal(sm.logits_for(sid),
                                          self.logits[j])
            np.testing.assert_array_equal(sm.tokens_for(sid),
                                          self.tokens[j, :pos])


def _carried_pool(arch, monkeypatch):
    """A four-slot pool behind a split pipeline, three sessions admitted,
    the host bookkeeping replayed beside it at every commit."""
    cfg = _cfg(arch, 4 if arch == "zamba2-7b" else 2)
    mgr, sm = make_session_manager(cfg, split=1, net=NetworkModel(1000.0),
                                   num_slots=4, max_seq=32, seed=0)
    host = _HostCarry(sm)
    orig = SessionManager.commit_step

    def commit_step(self, token, new_state, bounds, logits):
        host.commit(self, token, bounds, logits)
        orig(self, token, new_state, bounds, logits)
    monkeypatch.setattr(SessionManager, "commit_step", commit_step)
    for p in _ragged(cfg, (5, 9, 3), seed=12):
        host.admit(sm, sm.admit(p), p)
    return mgr, sm, host


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "zamba2-7b"])
def test_device_carry_bit_identical_to_host_bookkeeping(arch, monkeypatch):
    """Tokens, ``logits_for`` and every checkpoint row equal the host
    bookkeeping's exactly, through steps, a park and a readmit into
    another slot; the dead slot's rows stay zero throughout."""
    mgr, sm, host = _carried_pool(arch, monkeypatch)

    def dead_rows_zero(j):
        assert not np.asarray(sm.carry["bounds"])[j].any()
        assert not np.asarray(sm.carry["logits"])[j].any()

    for _ in range(3):
        mgr.active.process()
        host.check(sm)
        dead_rows_zero(3)
    a = sm.session_ids()[0]
    j = sm.slot_info(a).index
    sm.evict(a)
    host.park(j, a)
    host.check(sm)
    mgr.active.process()
    host.check(sm)
    dead_rows_zero(j)
    c = _ragged(sm.cfg, (7,), seed=15)[0]
    host.admit(sm, sm.admit(c), c)           # into the parked slot
    mgr.active.process()
    sm.readmit(a)                            # into the one left dead
    assert sm.slot_info(a).index == 3
    host.readmit(3, a)
    host.check(sm)
    for _ in range(2):
        mgr.active.process()
        host.check(sm)
    mgr.close()


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "zamba2-7b"])
def test_recompute_from_device_checkpoints(arch, monkeypatch):
    """``recompute_layers`` reads the device checkpoints: every live
    slot's rebuilt state equals its decoded state (to the float tolerance
    of the other recompute tests: a prefill and a decode step order their
    sums differently; a dead slot's row holds its masked garbage, and is
    rebuilt to zero), and the served tokens carry on equal to the host
    bookkeeping's."""
    mgr, sm, host = _carried_pool(arch, monkeypatch)
    for _ in range(3):
        mgr.active.process()
    live = [sm.slot_info(s).index for s in sm.session_ids()]
    nu = sm.runner.num_units
    decoded = {k: np.asarray(v) for k, v in sm.subset(1, nu).items()}
    sm.recompute_layers(1, nu)
    for k, v in sm.subset(1, nu).items():
        np.testing.assert_allclose(np.asarray(v)[live], decoded[k][live],
                                   atol=1e-4, err_msg=k)
    for _ in range(2):
        mgr.active.process()
        host.check(sm)
    mgr.close()


def test_snapshot_survives_donating_steps(tf_runner):
    """Steps, admissions and parks donate the carry; a snapshot taken
    before them still reads, and restoring it (twice) replays the same
    continuation."""
    sm = SessionManager(tf_runner, num_slots=3)
    a, b = [sm.admit(p) for p in _ragged(tf_runner.cfg, (4, 6), seed=13)]
    sm.decode_step()
    snap = sm.snapshot()
    bounds0 = np.asarray(snap["bounds"]).copy()
    for _ in range(2):
        sm.decode_step()
    sm.evict(a)
    sm.admit(_ragged(tf_runner.cfg, (5,), seed=14)[0])
    sm.decode_step()
    np.testing.assert_array_equal(np.asarray(snap["bounds"]), bounds0)
    runs = []
    for _ in range(2):
        sm.restore(snap)
        for _ in range(2):
            sm.decode_step()
        runs.append({s: (sm.logits_for(s), sm.tokens_for(s))
                     for s in (a, b)})
    for s in (a, b):
        np.testing.assert_array_equal(runs[0][s][0], runs[1][s][0])
        np.testing.assert_array_equal(runs[0][s][1], runs[1][s][1])
    np.testing.assert_array_equal(np.asarray(snap["bounds"]), bounds0)


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
