"""Sharded cloud stage: sharding rules on fake multi-device CPU meshes,
the first-class PipelineKey API, and mesh-shape-changing repartitions
(SimPool: every registered strategy; real pipelines: logits parity and
reshard accounting).

The device-hungry cases need the process launched with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and skip
otherwise: the flag is deliberately NOT set suite-wide (it changes XLA
CPU numerics enough to break the bit-exact split-invariance tests), so
``ci.sh`` runs this module a second time in its own flagged process."""
import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import INPUT_SHAPES, get_config
from repro.core import NetworkModel, PipelineManager, StageRunner
from repro.core.pool import PipelineKey, PoolKey
from repro.core.strategies import available_strategies
from repro.distributed import (ShardingDegraded, cache_shardings,
                               decode_state_shardings, input_shardings,
                               param_shardings)
from repro.launch.mesh import make_cloud_mesh
from repro.models import transformer as T
from repro.serving.sim import SimPool, SimRunner

needs_devices = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "in the environment before jax initialises (ci.sh runs this "
           "module that way in a dedicated process)")


def _spec_of(shardings, path_suffix: str):
    """PartitionSpec of the first leaf whose joined path ends with suffix."""
    for path, sh in jax.tree_util.tree_flatten_with_path(shardings)[0]:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", "")))
                        for p in path)
        if name.endswith(path_suffix):
            return sh.spec
    raise KeyError(path_suffix)


# ---------------------------------------------------------------------------
# PipelineKey API (satellite: first-class pool keys)
# ---------------------------------------------------------------------------

def test_pipeline_key_frozen_and_normalized():
    k = PipelineKey(split=3, mesh_shape=[2, 4])
    assert k.mesh_shape == (2, 4) and isinstance(k.mesh_shape, tuple)
    assert k.owns_weights is False and k.variant == ""
    with pytest.raises(dataclasses.FrozenInstanceError):
        k.split = 5
    assert PoolKey is PipelineKey          # deprecated alias still imports


def test_pipeline_key_legacy_tuple_shim():
    with pytest.warns(DeprecationWarning, match="tuple pool keys"):
        k = PipelineKey.of((2, True))
    assert k == PipelineKey(split=2, owns_weights=True, mesh_shape=None)
    with warnings.catch_warnings():
        warnings.simplefilter("error")     # passthrough must not warn
        assert PipelineKey.of(k) is k
    with pytest.raises(TypeError, match="not a pool key"):
        PipelineKey.of("nope")


def test_pool_make_key_fills_default_mesh():
    pool = SimPool(SimRunner(8), NetworkModel(20.0))
    try:
        assert pool.make_key(1).mesh_shape is None
        pool.set_mesh_shape((2,))
        assert pool.make_key(1).mesh_shape == (2,)
        # explicit always wins over the pool default — including an
        # explicit "no mesh"
        assert pool.make_key(1, mesh_shape=(4,)).mesh_shape == (4,)
        assert pool.make_key(1, mesh_shape=None).mesh_shape is None
    finally:
        pool.close()


def test_pool_accepts_legacy_tuple_keys():
    pool = SimPool(SimRunner(8), NetworkModel(20.0))
    try:
        entry, _ = pool.ensure(PipelineKey(split=2, owns_weights=True))
        with pytest.warns(DeprecationWarning, match="tuple pool keys"):
            assert pool.has((2, True))
        with pytest.warns(DeprecationWarning, match="tuple pool keys"):
            pool.release((2, True))
    finally:
        pool.close()


# ---------------------------------------------------------------------------
# sharding rules on fake 2/4/8-device meshes
# ---------------------------------------------------------------------------

@needs_devices
@pytest.mark.parametrize("mesh_shape", [(2,), (4,), (8,), (2, 4)])
@pytest.mark.parametrize("arch", ["qwen2.5-3b", "qwen2-moe-a2.7b",
                                  "falcon-mamba-7b"])
def test_param_shardings_divide_on_real_meshes(arch, mesh_shape):
    """dense/GQA, moe and ssm params all get axis-dividing shardings on
    every CI mesh (the jit-argument requirement the guard enforces)."""
    cfg = get_config(arch)
    mesh = make_cloud_mesh(mesh_shape)
    ps = jax.eval_shape(
        functools.partial(T.init_model, cfg, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", ShardingDegraded)
        sh = param_shardings(cfg, mesh, ps, shard_fsdp=False)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for leaf, s in zip(jax.tree.leaves(ps), jax.tree.leaves(sh)):
        for dim, ax in enumerate(s.spec):
            if ax is None:
                continue
            n = int(np.prod([sizes[a] for a in
                             (ax if isinstance(ax, tuple) else (ax,))]))
            assert leaf.shape[dim] % n == 0, (s.spec, leaf.shape)


@needs_devices
def test_param_shardings_use_model_axis():
    cfg = get_config("qwen2.5-3b")
    mesh = make_cloud_mesh((4,))
    ps = jax.eval_shape(
        functools.partial(T.init_model, cfg, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    sh = param_shardings(cfg, mesh, ps, shard_fsdp=False)
    assert _spec_of(sh, "wq")[-1] == "model"        # column-parallel
    assert _spec_of(sh, "wo")[-2] == "model"        # row-parallel
    assert "model" in _spec_of(sh, "embed")


@needs_devices
def test_param_shardings_guard_warns_not_silent():
    """A dim that does not divide the axis degrades to replication WITH a
    structured warning naming the leaf (was: silent replication)."""
    cfg = get_config("qwen2.5-3b")
    mesh = make_cloud_mesh((4,))
    odd = {"layers": {"attn": {"wq": jax.ShapeDtypeStruct((64, 13),
                                                          jnp.bfloat16)}}}
    with pytest.warns(ShardingDegraded, match=r"wq\[dim 1\]=13"):
        sh = param_shardings(cfg, mesh, odd, shard_fsdp=False)
    assert _spec_of(sh, "wq") == jax.sharding.PartitionSpec(None, None)


@needs_devices
def test_input_and_cache_shardings_on_2d_mesh():
    cfg = get_config("qwen2.5-3b")
    mesh = make_cloud_mesh((2, 4))
    shape = INPUT_SHAPES["decode_32k"]
    inp = {"tokens": jax.ShapeDtypeStruct((shape.global_batch, 1),
                                          jnp.int32)}
    ish = input_shardings(cfg, mesh, inp, shape)
    assert ish["tokens"].spec[0] == "data"          # batch -> dp
    cache = jax.eval_shape(
        lambda: T.init_cache(cfg, shape.global_batch, 128,
                             dtype=jnp.bfloat16))
    csh = cache_shardings(cfg, mesh, cache, shape)
    assert jax.tree.structure(csh) == jax.tree.structure(cache)


@needs_devices
def test_decode_state_shardings_rules():
    """Live-session layouts: kv heads -> tp when divisible, GQA caches
    with fewer kv heads replicated (the per-shard decode kernel needs
    whole heads, so head_dim is never split), conv channel dim, ssm
    channel dim; dp always replicated."""
    cfg = get_config("qwen2.5-3b")
    mesh = make_cloud_mesh((4,))
    st = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    state = {
        "k0": st(1, 8, 64, 128),      # KH=8 divides tp=4 -> dim 1
        "v1": st(1, 2, 64, 128),      # GQA KH=2: replicated, with a warning
        "conv0": st(1, 3, 256),       # channels (last dim) -> tp
        "ssm0": st(1, 256, 16),       # mamba channel dim 1 -> tp
    }
    with pytest.warns(ShardingDegraded, match=r"v1\[dim 1\]=2"):
        sh = decode_state_shardings(cfg, mesh, state)
    P = jax.sharding.PartitionSpec
    assert sh["k0"].spec == P(None, "model", None, None)
    assert sh["v1"].spec == P(None, None, None, None)
    assert sh["conv0"].spec == P(None, None, "model")
    assert sh["ssm0"].spec == P(None, "model", None)


@needs_devices
def test_decode_state_shardings_degrade_warns():
    cfg = get_config("qwen2.5-3b")
    mesh = make_cloud_mesh((4,))
    state = {"k0": jax.ShapeDtypeStruct((1, 3, 64, 7), jnp.float32)}
    with pytest.warns(ShardingDegraded, match="k0"):
        sh = decode_state_shardings(cfg, mesh, state)
    assert sh["k0"].spec == jax.sharding.PartitionSpec(None, None, None,
                                                       None)


# ---------------------------------------------------------------------------
# mesh-shape-changing repartitions: every registered strategy (SimPool)
# ---------------------------------------------------------------------------

def test_mesh_change_recorded_by_every_strategy():
    """set_mesh_shape + repartition (any strategy) -> the switch report
    carries the resharding wall and the mesh transition."""
    for name in sorted(available_strategies()):
        pool = SimPool(SimRunner(8), NetworkModel(20.0))
        mgr = PipelineManager(pool.runner, split=1, net=pool.net,
                              sample_inputs=None, pool=pool)
        try:
            mgr.set_mesh_shape((2,))
            mgr.build_standby(2)       # switch_a needs a live standby
            rep = mgr.repartition(name, 2)
            assert rep.old_mesh is None and rep.new_mesh == (2,), name
            assert rep.mesh_change and rep.t_reshard >= 0.0, name
            assert pool.reshards and \
                pool.reshards[-1].new_mesh == (2,), name
            # same mesh back-switch: no transition recorded
            rep2 = mgr.repartition(name if name != "switch_a"
                                   else "switch_b1", 1)
            assert not rep2.mesh_change and rep2.t_reshard == 0.0, name
        finally:
            mgr.close()


# ---------------------------------------------------------------------------
# real pipelines: sharded-vs-single-device parity + reshard accounting
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("qwen2.5-3b").reduced()
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    runner = StageRunner(cfg, params)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                         cfg.vocab_size))
    return runner, {"tokens": toks}


@needs_devices
def test_sharded_logits_match_single_device(tiny):
    runner, inputs = tiny
    mgr = PipelineManager(runner, split=1, net=NetworkModel(20.0),
                          sample_inputs=inputs)
    try:
        ref, _ = mgr.serve(inputs)
        mgr.set_mesh_shape((2,))
        rep = mgr.repartition("switch_b2", 1)
        assert rep.mesh_change and rep.new_mesh == (2,)
        assert rep.t_reshard >= 0.0
        out, _ = mgr.serve(inputs)
        # all-reduce reorders float sums: numerical, not bit, equality
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
    finally:
        mgr.close()


@needs_devices
def test_stateful_mesh_roundtrip_decodes_identically():
    """Decode streams with and without a mid-stream hop onto a 2-way mesh
    (and back) must emit the same tokens; both mesh transitions record a
    reshard on their reports."""
    from repro.core.stateful import make_stateful_manager
    cfg = get_config("qwen2.5-3b").reduced()
    net = NetworkModel(50.0)

    mgr, sess = make_stateful_manager(cfg, split=1, net=net, prompt_len=8,
                                      max_seq=32, seed=3)
    try:
        ref = [np.asarray(mgr.serve(None)[0]) for _ in range(4)]
        ref_toks = sess.tokens.copy()
    finally:
        mgr.close()

    mgr, sess = make_stateful_manager(cfg, split=1, net=net, prompt_len=8,
                                      max_seq=32, seed=3)
    try:
        out = [np.asarray(mgr.serve(None)[0])]
        mgr.set_mesh_shape((2,))
        r1 = mgr.repartition("switch_b2", 1)
        out.append(np.asarray(mgr.serve(None)[0]))
        mgr.set_mesh_shape(None)
        r2 = mgr.repartition("switch_b2", 1)
        out += [np.asarray(mgr.serve(None)[0]) for _ in range(2)]
        toks = sess.tokens.copy()
    finally:
        mgr.close()

    assert r1.mesh_change and r1.new_mesh == (2,)
    assert r2.mesh_change and r2.old_mesh == (2,) and r2.new_mesh is None
    np.testing.assert_array_equal(toks, ref_toks)
    for a, b in zip(out, ref):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@needs_devices
def test_slot_pool_mesh_roundtrip_decodes_identically():
    """A slot pool's step carry lives on the edge device: with a
    mid-stream hop onto a 2-way mesh and back, the sharded cloud stage's
    bounds and logits reach the commit there, and every session is served
    the tokens of a pool that never left one device."""
    from repro.serving import make_session_manager
    cfg = get_config("qwen2.5-3b").reduced()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (6, 9)]

    def run(hop):
        mgr, sm = make_session_manager(cfg, split=1, net=NetworkModel(50.0),
                                       num_slots=3, max_seq=32, seed=3)
        try:
            sids = [sm.admit(p) for p in prompts]
            mgr.serve({})
            if hop:
                mgr.set_mesh_shape((2,))
                assert mgr.repartition("switch_b2", 1).new_mesh == (2,)
            mgr.serve({})
            for v in sm.carry.values():          # still on the edge device
                assert v.sharding.device_set == {jax.devices()[0]}
            if hop:
                mgr.set_mesh_shape(None)
                assert mgr.repartition("switch_b2", 1).mesh_change
            for _ in range(2):
                mgr.serve({})
            return {s: (sm.tokens_for(s), sm.logits_for(s)) for s in sids}
        finally:
            mgr.close()

    ref, out = run(False), run(True)
    for s, (toks, logits) in ref.items():
        np.testing.assert_array_equal(out[s][0], toks)
        np.testing.assert_allclose(out[s][1], logits, rtol=1e-4, atol=1e-4)
