"""Weights made from the seed, on the device, in one jitted call.

The benchmark makes the weights (not the program), in the tree layout the
program takes, so that the plain references can make the very same
values again from the seed after the program's state is freed.  Values
follow the published initialisations closely enough for sane
activations: He-normal convolutions, 1/fan dense layers, N(0, 0.02)
transformer matrices; biases and norm scales are drawn too, so that a
reference or program that ignores them is caught.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int):
    """A key from a seed of up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# CNN (VGG-style: conv / pool / flatten / dense)
# ---------------------------------------------------------------------------

def cnn_shapes(cfg: dict) -> list:
    """Per layer: the (w, b) shapes, or None for a layer without weights."""
    hw, ch = cfg["input_hw"], cfg["input_ch"]
    k, out = cfg["kernel"], []
    for kind, *arg in cfg["layers"]:
        if kind == "conv":
            out.append(((k, k, ch, arg[0]), (arg[0],)))
            ch = arg[0]
        elif kind == "pool":
            hw //= cfg["pool"]
            out.append(None)
        elif kind == "flatten":
            ch, hw = hw * hw * ch, 1
            out.append(None)
        elif kind == "dense":
            out.append(((ch, arg[0]), (arg[0],)))
            ch = arg[0]
        else:
            raise ValueError(kind)
    return out


@functools.partial(jax.jit, static_argnums=(0, 2))
def _cnn_params(shapes, key, dtype):
    params = []
    for i, s in enumerate(shapes):
        if s is None:
            params.append({})
            continue
        (ws, bs) = s
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        fan = int(np.prod(ws[:-1]))
        gain = 2.0 if len(ws) == 4 else 1.0
        params.append({
            "w": (jax.random.normal(kw, ws, jnp.float32)
                  * np.sqrt(gain / fan)).astype(dtype),
            "b": (jax.random.normal(kb, bs, jnp.float32) * 0.01).astype(dtype)})
    return params


def cnn_params(cfg: dict, seed: int, dtype=jnp.float32) -> list:
    shapes = tuple(None if s is None else (tuple(s[0]), tuple(s[1]))
                   for s in cnn_shapes(cfg))
    return _cnn_params(shapes, base_key(seed), jnp.dtype(dtype))


def images(cfg: dict, seed: int, n: int):
    """``n`` seeded input frames, ``(n, H, W, C)`` float32, on the device."""
    key = jax.random.fold_in(base_key(seed), 1 << 20)
    return jax.jit(lambda k: jax.random.normal(
        k, (n, cfg["input_hw"], cfg["input_hw"], cfg["input_ch"]),
        jnp.float32))(key)


# ---------------------------------------------------------------------------
# Qwen2-style decoder (RMSNorm, GQA with QKV bias, SwiGLU, tied head)
# ---------------------------------------------------------------------------

def _lm_dims(cfg: dict):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = d // H
    return d, H, cfg["num_key_value_heads"], hd, cfg["intermediate_size"]


def _lm_layer(cfg: dict, key, dtype):
    d, H, KH, hd, F = _lm_dims(cfg)
    ks = jax.random.split(key, 12)

    def n(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    return {
        "ln1": {"scale": (1.0 + n(ks[0], (d,), 0.1)).astype(dtype)},
        "attn": {"wq": n(ks[1], (d, H * hd), 0.02),
                 "wk": n(ks[2], (d, KH * hd), 0.02),
                 "wv": n(ks[3], (d, KH * hd), 0.02),
                 "wo": n(ks[4], (H * hd, d), 0.02),
                 "bq": n(ks[5], (H * hd,), 0.02),
                 "bk": n(ks[6], (KH * hd,), 0.02),
                 "bv": n(ks[7], (KH * hd,), 0.02)},
        "ln2": {"scale": (1.0 + n(ks[8], (d,), 0.1)).astype(dtype)},
        "mlp": {"w_gate": n(ks[9], (d, F), 0.02),
                "w_up": n(ks[10], (d, F), 0.02),
                "w_down": n(ks[11], (F, d), 0.02)},
    }


@functools.partial(jax.jit, static_argnums=(0, 2))
def _lm_params(cfg_items, key, dtype):
    cfg = dict(cfg_items)
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    layers = jax.vmap(lambda i: _lm_layer(cfg, jax.random.fold_in(key, 1 + i),
                                          dtype))(jnp.arange(L))
    kf = jax.random.fold_in(key, L + 1)
    return {
        "embed": (jax.random.normal(jax.random.fold_in(key, 0), (V, d),
                                    jnp.float32) * 0.02).astype(dtype),
        "final_norm": {"scale": (1.0 + 0.1 * jax.random.normal(
            kf, (d,), jnp.float32)).astype(dtype)},
        "layers": layers,
    }


LM_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
           "intermediate_size", "num_hidden_layers", "vocab_size")


def lm_params(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    """The whole model, layers stacked on a leading axis (the program's
    layout), made on the device in one jitted call."""
    items = tuple((k, cfg[k]) for k in LM_KEYS)
    return _lm_params(items, base_key(seed), jnp.dtype(dtype))
