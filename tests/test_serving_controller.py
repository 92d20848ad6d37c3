"""Serving loop + NeukonfigController end-to-end behaviour."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (BandwidthTrace, NetworkModel, NeukonfigController,
                        PipelineManager, StageRunner, profile_transformer)
from repro.core.profiler import ModelProfile, UnitProfile
from repro.data import FrameSource
from repro.models import transformer as T
from repro.serving import BatchingServer, Request


def test_batching_server_decodes():
    cfg = get_config("qwen2.5-3b").reduced()
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    srv = BatchingServer(cfg, params, max_seq=64)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, (6 + i,)),
                    max_new_tokens=4) for i in range(3)]
    out = srv.run_batch(reqs)
    assert set(out) == {0, 1, 2}
    assert all(len(v) == 4 for v in out.values())
    assert all(0 <= t < cfg.vocab_size for v in out.values() for t in v)


def test_batching_server_breaks_decode_loop_when_all_done():
    """Resumed requests arriving with partial output must not burn the full
    ``steps - 1`` decode iterations once every request is done."""
    cfg = get_config("qwen2.5-3b").reduced()
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    srv = BatchingServer(cfg, params, max_seq=64)
    calls = {"n": 0}
    real_decode = srv._decode

    def counting_decode(*a, **kw):
        calls["n"] += 1
        return real_decode(*a, **kw)

    srv._decode = counting_decode
    rng = np.random.default_rng(0)
    reqs = [Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, (6,)),
                    max_new_tokens=8, output=[1] * 7),     # needs 1 token
            Request(rid=1, prompt=rng.integers(0, cfg.vocab_size, (5,)),
                    max_new_tokens=8, output=[2] * 8)]     # already done
    out = srv.run_batch(reqs)
    assert calls["n"] == 0          # prefill finished both; loop broke out
    assert len(out[0]) == 8 and out[1] == [2] * 8


def test_batching_server_migrates_mid_stream():
    """``export_state`` after two decode steps is host numpy (the slot
    pool's device carry included), and a second server that imports it
    finishes every request with the tokens of an unmigrated run."""
    cfg = get_config("qwen2.5-3b").reduced()
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, (5 + 2 * i,))
               for i in range(3)]

    def reqs():
        return [Request(rid=i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]

    whole = BatchingServer(cfg, params, max_seq=64).run_batch(reqs())
    src = BatchingServer(cfg, params, max_seq=64)
    first = reqs()
    src.run_batch(first, max_steps=2)
    state = src.export_state(first)
    assert all(isinstance(state[k], np.ndarray)
               for k in ("tok", "bounds", "logits"))
    dst = BatchingServer(cfg, params, max_seq=64)
    assert dst.run_batch(dst.import_state(state), resume=True) == whole


def test_frame_source_rate():
    cfg = get_config("qwen2.5-3b").reduced()
    src = FrameSource(cfg, fps=10, seq=8)
    frames = list(src.frames(duration=2.0))
    assert len(frames) == 20
    assert frames[1].t_arrival == pytest.approx(0.1)


def _toy_profile():
    """Profile whose optimum differs at 20 vs 5 Mbps."""
    units = [UnitProfile("embed", 0, 0, 4_000_000)]
    units += [UnitProfile(f"l{i}", 0.02, 0.005, b)
              for i, b in enumerate([2_000_000, 1_000_000, 100_000])]
    units += [UnitProfile("head", 0.02, 0.005, 0)]
    return ModelProfile("toy", units)


def test_controller_repartitions_on_trace():
    """The full loop: bandwidth change -> new optimum -> dynamic switch."""
    cfg = get_config("qwen2.5-3b").reduced()
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    runner = StageRunner(cfg, params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                              cfg.vocab_size)
    inputs = {"tokens": toks}
    mgr = PipelineManager(runner, split=1, net=NetworkModel(20.0),
                          sample_inputs=inputs)
    profile = _toy_profile()
    trace = BandwidthTrace(steps=[(0.0, 20.0), (5.0, 0.5)])
    ctl = NeukonfigController(mgr, profile, trace, strategy="switch_b2",
                              poll_dt=1.0)
    events = ctl.run(duration=10.0)
    switched = [e for e in events if e.report is not None]
    assert len(switched) == 1
    ev = switched[0]
    assert ev.new_split != ev.old_split
    assert mgr.active.split == ev.new_split
    # service continuity after the switch
    out, timing = mgr.serve(inputs)
    assert out.shape[-1] == cfg.vocab_size


def test_controller_no_switch_on_stable_network():
    cfg = get_config("qwen2.5-3b").reduced()
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    runner = StageRunner(cfg, params)
    inputs = {"tokens": jnp.zeros((1, 8), jnp.int32)}
    mgr = PipelineManager(runner, split=1, net=NetworkModel(20.0),
                          sample_inputs=inputs)
    ctl = NeukonfigController(mgr, _toy_profile(),
                              BandwidthTrace(steps=[(0.0, 20.0)]))
    events = ctl.run(duration=5.0)
    assert all(e.report is None for e in events)
