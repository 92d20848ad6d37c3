"""A decoder LM serving a slot pool of sessions: ``StatefulStageRunner``
-> ``SessionManager`` -> ``StatefulPipelinePool`` -> ``PipelineManager``
-> ``ServingEngine`` on a ``VirtualClock``.

Decode ticks arrive at a rate above what the model can step; every tick
that finds the edge free runs one real decode step for every slot.  The
population is closed: when a session has decoded its answer it ends
(``SessionManager.evict``) and the next one is admitted through the
engine (``execute_admit``), so both are charged to the stream clock.
The check that ends sessions runs before every tick (the engine's
controller seat, at the tick rate), so no session decodes past its
answer, and ``prompt max + answer max < max_seq`` makes a full context
impossible at any speed.

Correctness: after the window, a seeded sample of the finished sessions,
the longest among them, is run through the plain reference; each token
the system served is scored by how far its reference logit lies below
the reference's best at that position (its gap).  Two numbers are
compared: the widest gap over every checked token, and the share of
checked tokens that are not the reference's first choice.
"""
from __future__ import annotations

import gc
from dataclasses import dataclass

import numpy as np

from chipbench import trace as TR
from chipbench import traffic as T
from chipbench import weights
from chipbench.drivers import BenchEngine, program_config, schedule_link
from chipbench.reference import qwen2

# Widest gap (in logits) by which a served token's reference logit lies
# below the reference's best, over the checked tokens.  Set between chip
# readings of the program and of the bfloat16 control served in its place
# (``chipbench/control.py``); PERF.md gives the readings.
GAP_MAX_LIMIT = 1.0
# Share of checked tokens that are not the reference's first choice; set
# between the same readings.
MISMATCH_LIMIT = 0.3
SAMPLE = 32         # finished sessions checked per run


@dataclass(eq=False)
class Session:
    sid: str
    prompt_len: int
    answer: int
    cycle: int
    t_admit: float
    decoded: int = 0
    tokens: np.ndarray = None
    end: tuple = None                 # (cycle, stream t) at its end
    crossed_switch: bool = False


@dataclass
class _Lifecycle:
    """The engine's controller seat, used only for its tick: ends the
    sessions that have decoded their answers and admits the next ones."""
    driver: "Driver"
    poll_dt: float
    engine: object = None

    def attach(self, engine) -> None:
        self.engine = engine

    def network_events(self, duration):
        return []

    def on_network_event(self, t) -> None:
        pass

    def observe_tick(self, t) -> None:
        self.driver.turnover(self.engine)


class Driver:
    kind = "sessions"

    def __init__(self, cfg: dict, traffic: dict, seed: int, spans):
        self.cfg, self.traffic, self.seed, self.spans = cfg, traffic, seed, spans
        self.serving = cfg["serving"]
        self.steps = T.link_steps(traffic, self.serving["split_for_mbps"])
        if T.max_context(traffic) >= self.serving["max_seq"]:
            raise ValueError("prompt max + answer max must stay below "
                             "max_seq, or a context could fill")
        self.specs = T.session_specs(traffic, seed, cfg["vocab_size"])
        self.next_spec = 0
        self.live: dict = {}
        self.finished: list = []
        self.step_ctxs: list = []      # live context lengths, per step
        self.kernel_ops: set = set()   # trace names of the decode kernel
        self.recording = False
        self.cycle_index = -1
        self.attempted = self.failed = 0
        self.notes = {}

    # -- set-up ---------------------------------------------------------
    def setup(self) -> None:
        from repro.core import NetworkModel, PipelineManager
        from repro.core.stateful import (StatefulPipelinePool,
                                         StatefulStageRunner)
        from repro.serving.sessions import SessionManager

        cfg, sv = self.cfg, self.serving
        pcfg = program_config(cfg["program_config"], {
            "d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "num_layers": cfg["num_hidden_layers"],
            "vocab_size": cfg["vocab_size"], "rope_theta": cfg["rope_theta"],
            "norm_eps": cfg["rms_norm_eps"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "qkv_bias": cfg["qkv_bias"], "gated_mlp": True})
        params = weights.lm_params(cfg, self.seed)
        runner = StatefulStageRunner(pcfg, params, max_seq=sv["max_seq"],
                                     decode_impl=sv["decode_impl"])
        sm = SessionManager(runner, num_slots=sv["num_slots"])
        self.kv_itemsize = next(iter(sm.cache.values())).dtype.itemsize
        driver = self

        class Pool(StatefulPipelinePool):
            def _new_pipeline(self, key):
                pipe = super()._new_pipeline(key)
                inner = pipe.process

                def process(inputs=None, **kw):
                    if driver.recording and driver.spans.on:
                        driver.step_ctxs.append(
                            [sm.slot_info(s).pos + 1
                             for s in sm.session_ids()])
                    with driver.spans("step"):
                        return inner(inputs, **kw)
                pipe.process = process
                return pipe

        lat = float(self.traffic["latency_ms"])
        first, last = self.steps[0], self.steps[-1]
        net = NetworkModel(last[1], latency_ms=lat)
        pool = Pool(runner, net, {"tokens": None}, session=sm)
        self.mgr = PipelineManager(runner, last[2], net, {"tokens": None},
                                   pool=pool)
        self.sm, self.runner = sm, runner
        # fill every slot (the first admission also calibrates) and serve
        # a real step, whose host-side ops then compile before the window
        for _ in range(sv["num_slots"]):
            self._admit(None)
        self.mgr.serve({})
        # one session ended and a new one admitted: the eviction's and
        # admission's eager ops are compiled before the window
        sid = next(iter(self.live))
        self._end(sid, None)
        self._admit(None)
        strategy = self.traffic["strategy"]
        if strategy is not None and first[2] != last[2]:
            lo, hi = sorted((first[2], last[2]))
            for step in (first, last):
                self.mgr.set_network(NetworkModel(step[1], latency_ms=lat))
                self.mgr.repartition(strategy, step[2])
                self.mgr.serve({})
                self.kernel_ops |= TR.kernel_op_names(
                    [self.mgr.pool.active.edge_fn,
                     self.mgr.pool.active.cloud_fn],
                    b"flash_decode_attention")
            # both hand-off arms, whichever the planner picks in the window
            sm.recompute_layers(lo, hi)
            payload, _ = sm.export_layers(lo, hi)
            sm.import_layers(payload)
        self.active_split = last[2]
        self.kernel_ops |= TR.kernel_op_names(
            [self.mgr.pool.active.edge_fn, self.mgr.pool.active.cloud_fn],
            b"flash_decode_attention")
        self.notes["kernel_ops"] = sorted(self.kernel_ops)
        # the sessions of the set-up are the window's first population
        for s in self.live.values():
            s.cycle, s.t_admit = 0, 0.0
            s.decoded = sm.slot_info(s.sid).pos - s.prompt_len

    # -- sessions -------------------------------------------------------
    def _admit(self, engine) -> None:
        prompt, answer = self.specs[self.next_spec % len(self.specs)]
        sid = f"s{self.next_spec}"
        self.next_spec += 1
        self.attempted += self.recording
        try:
            if engine is None:
                self.sm.admit(prompt, sid=sid)
            else:
                engine.execute_admit(prompt, sid=sid)
        except (RuntimeError, ValueError) as e:   # refused: a failure
            self.failed += self.recording
            self.notes.setdefault("admission_errors", []).append(repr(e))
            return
        t = engine.clock.now() if engine is not None else 0.0
        self.live[sid] = Session(sid, len(prompt), answer,
                                 self.cycle_index, t)

    def _end(self, sid: str, engine) -> None:
        s = self.live.pop(sid)
        s.tokens = self.sm.tokens_for(sid)
        s.end = (self.cycle_index,
                 engine.clock.now() if engine is not None else 0.0)
        self.sm.evict(sid)
        # the program keeps an evicted session's state for a readmit; an
        # ended session never comes back, so its parked copy is dropped
        getattr(self.sm, "_parked", {}).pop(sid, None)
        if self.recording:
            self.finished.append(s)

    def turnover(self, engine) -> None:
        # every served step decodes one token for each session live in it
        recs = engine.timeline.records
        for r in recs[self._seen:]:
            for sid in (r.sessions or ()) if r.served else ():
                if sid in self.live:
                    self.live[sid].decoded += 1
        self._seen = len(recs)
        done = [sid for sid, s in self.live.items() if s.decoded >= s.answer]
        if not done:
            return
        with engine.clock.measure():    # ending blocks the serving loop
            for sid in done:
                self._end(sid, engine)
        for _ in done:
            self._admit(engine)

    # -- window ---------------------------------------------------------
    def start_window(self) -> None:
        self.recording = True

    def cycle(self, index: int) -> dict:
        from repro.serving import VirtualClock
        self.cycle_index = index
        rate = float(self.traffic["ticks"]["rate"])
        life = _Lifecycle(self, poll_dt=1.0 / rate)
        eng = BenchEngine(self.mgr, spans=self.spans, clock=VirtualClock(),
                          warmup=False, controller=life)
        self._seen = 0
        self.active_split = schedule_link(
            eng, self.steps, self.traffic["strategy"], self.active_split,
            float(self.traffic["latency_ms"]))
        dur = float(self.traffic["cycle_s"])
        self.turnover(eng)
        ticks = T.arrival_times(self.traffic, "ticks", self.seed, index)
        tl = eng.run([(t, {}) for t in ticks], duration=dur)
        return {"timeline": tl, "reports": list(eng.reports)}

    def stop_window(self) -> None:
        self.recording = False
        self.mgr.drain()

    # -- after the window -------------------------------------------------
    def counts(self, cycles: list) -> dict:
        recs = [r for c in cycles for r in c["timeline"].records]
        return {"ticks_arrived": len(recs),
                "steps_served": sum(r.served for r in recs),
                "ticks_dropped_busy": sum(r.drop_reason == "busy"
                                          for r in recs),
                "sessions_ended": len(self.finished),
                "sessions_live_at_end": len(self.live)}

    def free(self) -> None:
        self.mgr.close()
        del self.mgr, self.sm, self.runner
        gc.collect()

    def sample(self, cycles: list) -> list:
        """The finished sessions to check: the longest, the longest that
        lived across a repartition, and a seeded draw of the rest."""
        switch_t = {(i, w.t_start) for i, c in enumerate(cycles)
                    for w in c["timeline"].windows}
        for s in self.finished:
            s.crossed_switch = s.cycle != s.end[0] or any(
                c == s.cycle and s.t_admit <= t <= s.end[1]
                for c, t in switch_t)
        pool = sorted(self.finished, key=lambda s: -len(s.tokens))
        pick = pool[:1]
        crossed = [s for s in pool if s.crossed_switch and s not in pick]
        pick += crossed[:1]
        rest = [s for s in pool if s not in pick]
        rng = T.substream(self.seed, 5)
        for i in rng.permutation(len(rest))[:max(0, SAMPLE - len(pick))]:
            pick.append(rest[i])
        return pick

    def check(self, cycles: list) -> list:
        picked = self.sample(cycles)
        self.notes["sessions_checked"] = len(picked)
        self.notes["served_tokens_checked"] = int(sum(
            len(s.tokens) - s.prompt_len for s in picked))
        self.notes["checked_crossed_switch"] = int(sum(
            s.crossed_switch for s in picked))
        if not picked:
            return [("logit_gap_max", float("inf"), GAP_MAX_LIMIT)]
        params = weights.lm_params(self.cfg, self.seed)
        g = qwen2.gaps(self.cfg, params, [s.tokens for s in picked],
                       [s.prompt_len for s in picked], control=False)
        del params
        served = np.concatenate(g["served"])
        self.notes["logit_gap_mean"] = float(served.mean())
        self.notes["logit_gap_ms"] = float((served * served).mean())
        checks = [("logit_gap_max", float(served.max()), GAP_MAX_LIMIT),
                  ("served_mismatch_share", float((served > 0).mean()),
                   MISMATCH_LIMIT)]
        if self.steps[0][2] != self.steps[-1][2]:
            checks.append(("checked_sessions_without_switch",
                           float(self.notes["checked_crossed_switch"] == 0),
                           0.0))
        return checks
