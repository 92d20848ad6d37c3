"""The decode path's Pallas kernels compile for a TPU v5e at published
widths, and the sharded cloud stage compiles with them over four chips.

Nothing here runs: each case compiles for a described (not attached)
``v5e:2x2`` topology and asserts the Mosaic kernel is in the executable
(``tpu_custom_call``).  Interpret mode, which the CPU tests use, never
sees Mosaic's tiling rules or GSPMD's refusal to partition a kernel;
this file does.  The topology is described inside a fixture, never at
import, so every xdist worker collects the same tests.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs import get_config
from repro.core.stateful import StatefulStageRunner
from repro.distributed.sharding import (ShardingDegraded,
                                        decode_state_shardings,
                                        param_shardings)
from repro.kernels.flash_decode import flash_decode_attention
from repro.kernels.mamba_scan import mamba1_scan
from repro.kernels.ssd_scan import ssd_scan
from repro.models import transformer as T


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without
    # one: keep them out of any persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Kernels pick interpret mode from ``jax.default_backend()``, which
    is the CPU here: answer for the chip being compiled for."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, *avals):
    compiled = jax.jit(fn).lower(*avals).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("per_row", [False, True], ids=["scalar", "vector"])
def test_flash_decode_qwen25_3b(one_chip, per_row):
    B, KH, G, hd, S = 8, 2, 8, 128, 1024
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    pos = sd((B,) if per_row else (), jnp.int32)
    _compile(lambda q, k, v, p: flash_decode_attention(
        q, k, v, pos=p, interpret=False),
        sd((B, 1, KH * G, hd)), sd((B, KH, S, hd)), sd((B, KH, S, hd)), pos)


@pytest.mark.parametrize("S", [1, 1024])
def test_mamba1_scan_falcon_mamba_7b(one_chip, S):
    B, Di, N = 1, 8192, 16
    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
    _compile(functools.partial(mamba1_scan, interpret=False),
             sd(B, S, Di), sd(B, S, N), sd(B, S, N), sd(B, S, Di),
             sd(Di, N), sd(B, Di, N))


@pytest.mark.parametrize("S", [1, 1024])
def test_ssd_scan_zamba2_7b(one_chip, S):
    B, H, P, N = 1, 112, 64, 64
    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
    _compile(functools.partial(ssd_scan, interpret=False),
             sd(B, S, H), sd(B, S, N), sd(B, S, N), sd(B, S, H, P),
             sd(H), sd(B, H, P, N))


def test_sharded_cloud_decode_qwen25_3b(topo, on_tpu):
    """The cloud stage's decode executable over a (4,) mesh: each shard
    runs flash_decode on whole KV heads — qwen2.5-3b's 2 KV heads do not
    divide 4, so the cache is replicated rather than split on head_dim."""
    cfg = dataclasses.replace(get_config("qwen2.5-3b"), num_layers=4)
    mesh = Mesh(np.asarray(topo.devices[:4]), ("model",),
                axis_types=(jax.sharding.AxisType.Auto,))
    params = jax.eval_shape(functools.partial(T.init_model, cfg),
                            jax.random.PRNGKey(0))
    runner = StatefulStageRunner(cfg, params, max_seq=256,
                                 decode_impl="kernel")
    B, KH, hd = 4, cfg.num_kv_heads, cfg.head_dim
    kv = jax.ShapeDtypeStruct((B, KH, 256, hd), jnp.float32)
    cache = {f"{n}{i}": kv for i in range(2, 4) for n in "kv"}
    x = jax.ShapeDtypeStruct((B, 1, cfg.d_model), jnp.float32)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32)
    psh = param_shardings(cfg, mesh, params, shard_fsdp=False)
    with pytest.warns(ShardingDegraded, match="k2"):
        csh = decode_state_shardings(cfg, mesh, cache)
    repl = NamedSharding(mesh, PartitionSpec())
    compiled = runner.executable("decode", 2, 4, params, x, cache, pos,
                                 shardings=(psh, repl, csh, repl),
                                 mesh=mesh)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the weights really are split: every shard holds a quarter of them
    per_dev = compiled.memory_analysis().argument_size_in_bytes
    whole = sum(int(np.prod(a.shape)) * 4 for a in jax.tree.leaves(params))
    assert per_dev < whole / 2


@pytest.mark.parametrize("lo,hi", [(0, 18), (12, 36)])
def test_decode_range_reads_f32_weights_per_layer(one_chip, on_tpu, lo, hi):
    """A qwen2.5-3b decode range at 4 x 512 reads each layer's float32
    weights from the stacked parameter: no bf16 copy of the range's
    weights (``bf16[<layers>, ...]``) is made before the layers run, and
    the executable's temporaries stay far below such a copy (2.97 GB for
    18 layers, 4.01 GB for 24)."""
    cfg = get_config("qwen2.5-3b")
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    params = jax.tree.map(lambda a: sd(a.shape, a.dtype),
                          jax.eval_shape(functools.partial(T.init_model, cfg),
                                         jax.random.PRNGKey(0)))
    runner = StatefulStageRunner(cfg, params, max_seq=512,
                                 decode_impl="kernel")
    B, KH, hd = 4, cfg.num_kv_heads, cfg.head_dim
    cache = {f"{n}{i}": sd((B, KH, 512, hd))
             for i in range(lo, hi) for n in "kv"}
    compiled = runner.executable("decode", lo, hi, params,
                                 sd((B, 1, cfg.d_model)), cache,
                                 sd((B,), jnp.int32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"bf16[{hi - lo}," not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2**30
