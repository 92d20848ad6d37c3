"""Zamba2-style hybrid weights made from the seed, on the device, in the
program's tree layout (``repro.models.transformer.init_model``'s hybrid
branch): Mamba-2 layers stacked on a leading layer axis, the shared
blocks on a block axis, each application's adapter and output linear on
an application axis.

Layers are drawn one at a time inside one jitted call (``lax.map``), so
that making 11 GB of weights needs about one layer's temporaries beside
them.  Values follow the published initialisations closely enough for
sane activations: N(0, 0.02) matrices, Mamba's dt bias from a
log-uniform step in [1e-3, 1e-1] and A from uniform [1, 16], conv
weights of a 4-tap filter; norm scales, biases and D are drawn too, so
that a reference or program that ignores them is caught.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights import base_key

KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
        "attention_head_dim", "num_hidden_layers", "vocab_size",
        "mamba_expand", "mamba_headdim", "mamba_ngroups", "mamba_d_state",
        "mamba_d_conv", "num_mem_blocks", "adapter_rank", "n_apps")


def _n(k, shape, std, dtype):
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def _mamba_layer(c, key, dtype):
    d = c["hidden_size"]
    di = c["mamba_expand"] * d
    H = di // c["mamba_headdim"]
    conv = di + 2 * c["mamba_ngroups"] * c["mamba_d_state"]
    K = c["mamba_d_conv"]
    ks = jax.random.split(key, 10)
    step = jnp.exp(jax.random.uniform(ks[5], (H,), jnp.float32)
                   * (np.log(0.1) - np.log(1e-3)) + np.log(1e-3))
    return {
        "ln": {"scale": (1.0 + _n(ks[0], (d,), 0.1, jnp.float32)).astype(dtype)},
        "mamba": {
            "in_proj": _n(ks[1], (d, di + conv + H), 0.02, dtype),
            "conv_w": _n(ks[2], (K, conv), 1.0 / np.sqrt(K), dtype),
            "conv_b": _n(ks[3], (conv,), 0.02, dtype),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
            "A_log": jnp.log(jax.random.uniform(ks[6], (H,), jnp.float32,
                                                1.0, 16.0)).astype(dtype),
            "D": (1.0 + _n(ks[7], (H,), 0.1, jnp.float32)).astype(dtype),
            "norm": (1.0 + _n(ks[8], (di,), 0.1, jnp.float32)).astype(dtype),
            "out_proj": _n(ks[9], (di, d), 0.02, dtype)},
    }


def _block(c, key, dtype):
    d, F = c["hidden_size"], c["intermediate_size"]
    w = c["num_attention_heads"] * c["attention_head_dim"]
    ks = jax.random.split(key, 8)
    return {
        "ln1": {"scale": (1.0 + _n(ks[0], (2 * d,), 0.1, jnp.float32)).astype(dtype)},
        "attn": {"wq": _n(ks[1], (2 * d, w), 0.02, dtype),
                 "wk": _n(ks[2], (2 * d, w), 0.02, dtype),
                 "wv": _n(ks[3], (2 * d, w), 0.02, dtype),
                 "wo": _n(ks[4], (w, d), 0.02, dtype)},
        "ln2": {"scale": (1.0 + _n(ks[5], (d,), 0.1, jnp.float32)).astype(dtype)},
        "mlp": {"w_gate_up": _n(ks[6], (d, 2 * F), 0.02, dtype),
                "w_down": _n(ks[7], (F, d), 0.02, dtype)},
    }


def _app(c, key, dtype):
    d, F, r = c["hidden_size"], c["intermediate_size"], c["adapter_rank"]
    ks = jax.random.split(key, 3)
    return {"adapter_down": _n(ks[0], (d, r), 0.02, dtype),
            "adapter_up": _n(ks[1], (r, 2 * F), 0.02, dtype),
            "linear": _n(ks[2], (d, d), 0.02, dtype)}


@functools.partial(jax.jit, static_argnums=(0, 2))
def _params(items, key, dtype):
    c = dict(items)
    d, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]

    def stack(make, n, salt):
        return jax.lax.map(
            lambda i: make(c, jax.random.fold_in(jax.random.fold_in(
                key, salt), i), dtype), jnp.arange(n))

    return {
        "embed": _n(jax.random.fold_in(key, 0), (V, d), 0.02, dtype),
        "final_norm": {"scale": (1.0 + _n(jax.random.fold_in(key, 1), (d,),
                                          0.1, jnp.float32)).astype(dtype)},
        "layers": stack(_mamba_layer, L, 2),
        "shared": stack(_block, c["num_mem_blocks"], 3),
        "apps": stack(_app, c["n_apps"], 4),
    }


def hybrid_params(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    """The whole model in the program's layout, made on the device in one
    jitted call."""
    L = cfg["num_hidden_layers"]
    c = dict(cfg, n_apps=sum(i < L for i in cfg["hybrid_layer_ids"]))
    items = tuple((k, c[k]) for k in KEYS)
    return _params(items, base_key(seed), jnp.dtype(dtype))
