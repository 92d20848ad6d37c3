"""Decode hot-path parity: rolled lax.scan ranges + Pallas kernel routing.

The serving decode path has two orthogonal knobs on
``StatefulStageRunner`` — ``rolled`` (lax.scan over stacked per-layer
weights for full-sequence ranges vs the unrolled Python-loop trace;
decode ranges read each layer through a static index either way) and
``decode_impl``
(``flash_decode``/``mamba_scan``/``ssd_scan`` Pallas kernels vs the XLA
reference ops).  Every combination must produce the same logits AND the
same exported hand-off state layout, for all four families (plus a GQA
shape), in interpret mode on CPU — otherwise a repartition could hand
state between pipelines built on different paths and serve garbage.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.stateful import (HANDOFF_META_KEY, DecodeSession,
                                 StatefulStageRunner)
from repro.models import transformer as T

MAX_SEQ = 32
PROMPT = 8
STEPS = 3

# name -> (arch, cfg overrides); GQA: 4 heads over 2 kv heads
CASES = {
    "dense": ("qwen2.5-3b", {}),
    "dense_gqa": ("qwen2.5-3b", {"num_kv_heads": 2}),
    "moe": ("qwen2-moe-a2.7b", {}),
    "ssm": ("falcon-mamba-7b", {}),
    "hybrid": ("zamba2-7b", {}),
}


def _cfg(name):
    arch, kw = CASES[name]
    return dataclasses.replace(get_config(arch).reduced(), num_layers=3,
                               **kw)


def _run_path(cfg, params, *, decode_impl, rolled):
    """Prefill + STEPS decode steps through a mid-split two-stage stack;
    returns (stacked logits, export payload, payload bytes)."""
    r = StatefulStageRunner(cfg, params, max_seq=MAX_SEQ,
                            decode_impl=decode_impl, rolled=rolled)
    s = DecodeSession(r)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, PROMPT), 0,
                              cfg.vocab_size)
    s.prefill(toks)
    U = len(r.units)
    mid = U // 2
    av = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype), t)
    logits = [np.asarray(s.last_logits)]
    for _ in range(STEPS):
        tok = s.next_token()
        x = r.stream(r.params["embed"][jnp.asarray(tok, jnp.int32)])
        pos = jnp.int32(s.pos)
        fe = r.executable("decode", 0, mid, r.params, av(x),
                          s.subset(0, mid), av(pos))
        fc = r.executable("decode", mid, U, r.params, av(x),
                          s.subset(mid, U), av(pos))
        xe, ne, be = fe(r.params, x, s.subset(0, mid), pos)
        xc, nc, bc = fc(r.params, xe, s.subset(mid, U), pos)
        lg = (T._apply_norm(cfg, r.params["final_norm"], r.hidden(xc))[:, -1]
              @ T.lm_head_weights(cfg, r.params)).astype(jnp.float32)
        s.commit_step(tok, {**ne, **nc}, jnp.concatenate([be, bc], 0), lg)
        logits.append(np.asarray(lg))
    payload, nbytes = s.export_layers(0, cfg.num_layers)
    return np.concatenate(logits, 0), payload, nbytes


def _assert_same_export(p, n, p_ref, n_ref, atol):
    """Same hand-off surface: identical keys/dtypes/shapes/byte counts,
    values within tolerance."""
    assert n == n_ref
    assert set(p) == set(p_ref)
    for k in p_ref:
        if k == HANDOFF_META_KEY:
            continue
        dt, shape, buf = p[k]
        dt0, shape0, buf0 = p_ref[k]
        assert (dt, tuple(shape), len(buf)) == (dt0, tuple(shape0),
                                                len(buf0)), k
        np.testing.assert_allclose(
            np.frombuffer(buf, dt).reshape(shape).astype(np.float64),
            np.frombuffer(buf0, dt0).reshape(shape0).astype(np.float64),
            atol=atol, err_msg=k)


@pytest.mark.parametrize("name", list(CASES))
def test_rolled_and_kernel_paths_match_reference(name):
    cfg = _cfg(name)
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    ref, p_ref, n_ref = _run_path(cfg, params, decode_impl="reference",
                                  rolled=False)
    rolled, p_roll, n_roll = _run_path(cfg, params,
                                       decode_impl="reference",
                                       rolled=True)
    kern, p_kern, n_kern = _run_path(cfg, params, decode_impl="kernel",
                                     rolled=True)
    np.testing.assert_allclose(rolled, ref, atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(kern, ref, atol=5e-4, rtol=1e-3)
    _assert_same_export(p_roll, n_roll, p_ref, n_ref, atol=5e-5)
    _assert_same_export(p_kern, n_kern, p_ref, n_ref, atol=5e-4)


def test_decode_impl_validation_and_auto_resolution():
    cfg = _cfg("dense")
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="decode_impl"):
        StatefulStageRunner(cfg, params, decode_impl="nope")
    r = StatefulStageRunner(cfg, params)
    assert r.decode_impl == "auto"
    want = "kernel" if jax.default_backend() == "tpu" else "reference"
    assert r.resolved_decode_impl == want
    # pinning survives auto resolution
    assert StatefulStageRunner(cfg, params,
                               decode_impl="kernel").resolved_decode_impl \
        == "kernel"


def test_calibrate_decode_reprices_optimal_split():
    """Measured per-token stage walls rescale the analytic profile so
    ``optimal_split`` prices the real (e.g. kernel-speed) stages."""
    from repro.core.network import NetworkModel
    from repro.core.partitioner import optimal_split
    from repro.core.profiler import calibrate_decode, profile_transformer

    cfg = dataclasses.replace(get_config("qwen2.5-3b").reduced(),
                              num_layers=8)
    prof = profile_transformer(cfg, seq=1)
    net = NetworkModel(1000.0, latency_ms=0.0)
    split0 = optimal_split(prof, net).split
    tok0 = prof.cache_token()

    class Timing:
        def __init__(self, e, c):
            self.t_edge, self.t_cloud = e, c

    pred_e, _, pred_c = prof.latency(1, net)
    # the edge stage measured 100x FASTER than the analytic profile
    # assumed (a kernel-speed edge), cloud as predicted
    se, sc = calibrate_decode(prof, [Timing(pred_e / 100, pred_c)] * 3,
                              split=1)
    assert abs(se - 0.01) < 1e-9 and abs(sc - 1.0) < 1e-9
    assert prof.cache_token() != tok0          # downstream memos dropped
    e2, _, c2 = prof.latency(1, net)
    assert abs(e2 - pred_e / 100) < 1e-12
    assert abs(c2 - pred_c) < 1e-12
    # a 100x-cheaper edge pulls the optimum deeper onto the edge
    assert optimal_split(prof, net).split >= split0


def test_calibrate_decode_degenerate_timings_are_noops():
    from repro.core.profiler import calibrate_decode, profile_transformer
    cfg = _cfg("dense")
    prof = profile_transformer(cfg, seq=1)

    class Timing:
        def __init__(self, e, c):
            self.t_edge, self.t_cloud = e, c

    # zero measurements must not zero the profile
    se, sc = calibrate_decode(prof, [Timing(0.0, 0.0)], split=1)
    assert se == 1.0 and sc == 1.0
