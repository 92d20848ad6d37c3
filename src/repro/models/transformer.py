"""Unified decoder model covering all assigned families.

Families:
  dense / moe        stacked attn(+moe) layers, scan-over-layers
  ssm                stacked mamba1 layers
  hybrid (zamba2)    mamba2 backbone; before each layer of
                     ``cfg.app_layers`` a shared transformer block runs over
                     concat(x, x0) (x0 the token embedding), the
                     applications alternating between ``num_mem_blocks``
                     blocks; each application has its own MLP adapter,
                     output linear and KV cache, and its output T enters
                     its layer as x + mamba(norm(x + T))
  vlm                dense LM consuming stub patch embeddings prepended to text
  audio (whisper)    encoder (bidirectional) + decoder (self + cross attention)

Three entry points:
  train_loss(cfg, params, batch)            full-seq fwd + chunked CE loss
  prefill(cfg, params, inputs, max_seq)     full-seq fwd -> (last_logits, cache)
  decode_step(cfg, params, token, cache)    one token against the cache

Params are plain dicts; homogeneous stacks are stacked on a leading L axis and
executed with lax.scan(+remat) so HLO size is depth-independent.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as Lyr
from repro.models import ssm as SSM


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _norm_params(cfg, d, dtype):
    if cfg.family == "audio":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype)}


def _apply_norm(cfg, p, x):
    if cfg.family == "audio":
        return Lyr.layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)
    return Lyr.rms_norm(x, p["scale"], cfg.norm_eps)


def init_attn_params(cfg, key, dtype):
    d, hd = cfg.d_model, cfg.head_dim
    H, KH = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    std = 0.02
    p = {
        "wq": jax.random.normal(ks[0], (d, H * hd), dtype) * std,
        "wk": jax.random.normal(ks[1], (d, KH * hd), dtype) * std,
        "wv": jax.random.normal(ks[2], (d, KH * hd), dtype) * std,
        "wo": jax.random.normal(ks[3], (H * hd, d), dtype) * std,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KH * hd,), dtype)
        p["bv"] = jnp.zeros((KH * hd,), dtype)
    return p


def init_mlp_params(cfg, key, dtype, d_ff=None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    std = 0.02
    if cfg.gated_mlp:
        return {"w_gate": jax.random.normal(ks[0], (d, f), dtype) * std,
                "w_up": jax.random.normal(ks[1], (d, f), dtype) * std,
                "w_down": jax.random.normal(ks[2], (f, d), dtype) * std}
    return {"w_up": jax.random.normal(ks[1], (d, f), dtype) * std,
            "w_down": jax.random.normal(ks[2], (f, d), dtype) * std}


def init_moe_params(cfg, key, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    std = 0.02
    E, F = m.num_experts, m.expert_d_ff
    p = {
        "router": jax.random.normal(ks[0], (d, E), jnp.float32) * std,
        "w_gate": jax.random.normal(ks[1], (E, d, F), dtype) * std,
        "w_up": jax.random.normal(ks[2], (E, d, F), dtype) * std,
        "w_down": jax.random.normal(ks[3], (E, F, d), dtype) * std,
    }
    if m.num_shared_experts:
        sks = jax.random.split(ks[4], 3)
        p["shared_w_gate"] = jax.random.normal(sks[0], (d, m.shared_d_ff), dtype) * std
        p["shared_w_up"] = jax.random.normal(sks[1], (d, m.shared_d_ff), dtype) * std
        p["shared_w_down"] = jax.random.normal(sks[2], (m.shared_d_ff, d), dtype) * std
    return p


def init_decoder_layer(cfg, key, dtype, *, cross=False):
    """One attention decoder layer (dense/moe/vlm/audio-decoder)."""
    ks = jax.random.split(key, 4)
    p = {"ln1": _norm_params(cfg, cfg.d_model, dtype),
         "attn": init_attn_params(cfg, ks[0], dtype),
         "ln2": _norm_params(cfg, cfg.d_model, dtype)}
    if cfg.moe is not None:
        p["moe"] = init_moe_params(cfg, ks[1], dtype)
    else:
        p["mlp"] = init_mlp_params(cfg, ks[1], dtype)
    if cross:
        p["ln_x"] = _norm_params(cfg, cfg.d_model, dtype)
        p["xattn"] = init_attn_params(cfg, ks[2], dtype)
    return p


def init_ssm_layer(cfg, key, dtype):
    kind = cfg.ssm.kind
    init = SSM.init_mamba1 if kind == "mamba1" else SSM.init_mamba2
    return {"ln": _norm_params(cfg, cfg.d_model, dtype),
            "mamba": init(cfg, key, dtype)}


def init_model(cfg, key, dtype=jnp.float32) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model), dtype) * 0.02,
        "final_norm": _norm_params(cfg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            ks[1], (cfg.d_model, cfg.vocab_size), dtype) * 0.02

    L = cfg.num_layers
    lkeys = jax.random.split(ks[2], L)
    if cfg.family in ("dense", "moe", "vlm"):
        params["layers"] = jax.vmap(
            lambda k: init_decoder_layer(cfg, k, dtype))(lkeys)
    elif cfg.family == "ssm":
        params["layers"] = jax.vmap(
            lambda k: init_ssm_layer(cfg, k, dtype))(lkeys)
    elif cfg.family == "hybrid":
        params["layers"] = jax.vmap(
            lambda k: init_ssm_layer(cfg, k, dtype))(lkeys)
        params.update(init_hybrid_params(cfg, ks[3], dtype))
    elif cfg.family == "audio":
        params["layers"] = jax.vmap(
            lambda k: init_decoder_layer(cfg, k, dtype, cross=True))(lkeys)
        ekeys = jax.random.split(ks[4], cfg.encoder.num_layers)
        params["encoder"] = {
            "layers": jax.vmap(lambda k: init_decoder_layer(cfg, k, dtype))(ekeys),
            "final_norm": _norm_params(cfg, cfg.d_model, dtype),
        }
    else:
        raise ValueError(cfg.family)

    if cfg.frontend == "vision":
        params["vision_proj"] = jax.random.normal(
            ks[5], (cfg.d_model, cfg.d_model), dtype) * 0.02
    return params


def init_hybrid_params(cfg, key, dtype):
    """Hybrid: the shared blocks, stacked over ``num_mem_blocks``
    (``shared``), and each application's MLP adapter and output linear,
    stacked over applications (``apps``)."""
    d, F, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank
    w = cfg.num_heads * cfg.head_dim
    std = 0.02

    def block(k):
        ks = jax.random.split(k, 6)
        return {"ln1": {"scale": jnp.ones((2 * d,), dtype)},
                "attn": {"wq": jax.random.normal(ks[0], (2 * d, w), dtype) * std,
                         "wk": jax.random.normal(ks[1], (2 * d, w), dtype) * std,
                         "wv": jax.random.normal(ks[2], (2 * d, w), dtype) * std,
                         "wo": jax.random.normal(ks[3], (w, d), dtype) * std},
                "ln2": {"scale": jnp.ones((d,), dtype)},
                "mlp": {"w_gate_up": jax.random.normal(
                            ks[4], (d, 2 * F), dtype) * std,
                        "w_down": jax.random.normal(ks[5], (F, d), dtype) * std}}

    def app(k):
        ks = jax.random.split(k, 3)
        return {"adapter_down": jax.random.normal(ks[0], (d, r), dtype) * std,
                "adapter_up": jax.random.normal(ks[1], (r, 2 * F), dtype) * std,
                "linear": jax.random.normal(ks[2], (d, d), dtype) * std}

    kb, ka = jax.random.split(key)
    return {"shared": jax.vmap(block)(jax.random.split(kb, cfg.num_mem_blocks)),
            "apps": jax.vmap(app)(jax.random.split(ka, len(cfg.app_layers)))}


def hybrid_block(cfg, params, g, x, x0, rope_cs, attend):
    """Application ``g`` of the shared transformer block (Zamba2): block
    ``g mod num_mem_blocks`` over concat(x, x0) -- RMSNorm, MHA with
    rope and scores over sqrt(head_dim / 2), RMSNorm, gated GELU MLP with
    the application's LoRA on gate_up -- then the application's linear.
    ``attend(q, k, v) -> (out, state)`` runs the attention over the
    roped heads (full sequence or against a cache).  Returns (T, state).
    Weights are read through static indices: no copy of a block."""
    p = jax.tree.map(lambda a: a[g % cfg.num_mem_blocks], params["shared"])
    ap = jax.tree.map(lambda a: a[g], params["apps"])
    B, S, _ = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    h = Lyr.rms_norm(jnp.concatenate([x, x0.astype(x.dtype)], -1),
                     p["ln1"]["scale"], cfg.norm_eps)
    a = p["attn"]
    q = (h @ a["wq"]).reshape(B, S, H, hd)
    k = (h @ a["wk"]).reshape(B, S, H, hd)
    v = (h @ a["wv"]).reshape(B, S, H, hd)
    cos, sin = rope_cs
    # the kernels scale scores by 1/sqrt(hd); Zamba2 by 1/sqrt(hd / 2)
    q = Lyr.apply_rope(q, cos, sin) * np.sqrt(2.0).astype(q.dtype)
    k = Lyr.apply_rope(k, cos, sin)
    att, state = attend(q, k, v)
    o = att.reshape(B, S, H * hd) @ a["wo"]
    h2 = Lyr.rms_norm(o, p["ln2"]["scale"], cfg.norm_eps)
    gu = h2 @ p["mlp"]["w_gate_up"] \
        + (h2 @ ap["adapter_down"]) @ ap["adapter_up"]
    gate, up = jnp.split(gu, 2, axis=-1)
    y = (jax.nn.gelu(gate, approximate=False) * up) @ p["mlp"]["w_down"]
    return y @ ap["linear"], state


def hybrid_layer(cfg, lp, x, t, cache=None, *, impl="jnp"):
    """A Mamba-2 layer of the hybrid: x + mamba(norm(x + t)), ``t`` the
    output of the application before it, or None."""
    h = _apply_norm(cfg, lp["ln"], x if t is None else x + t)
    y, new = SSM.mamba2_block(lp["mamba"], h, cache, cfg=cfg, impl=impl)
    return x + y, new


def hybrid_segments(cfg, lo: int, hi: int):
    """Layers [lo, hi) as ``("mamba", a, b)`` runs of plain Mamba layers
    and ``("app", i, g)`` for layer ``i`` preceded by application ``g``."""
    apps = {i: g for g, i in enumerate(cfg.app_layers)}
    segs = []
    for i in range(lo, hi):
        if i in apps:
            segs.append(("app", i, apps[i]))
        elif segs and segs[-1][0] == "mamba" and segs[-1][2] == i:
            segs[-1] = ("mamba", segs[-1][1], i + 1)
        else:
            segs.append(("mamba", i, i + 1))
    return segs


# ---------------------------------------------------------------------------
# full-sequence blocks
# ---------------------------------------------------------------------------

def _project_qkv(cfg, p, h):
    B, S, _ = h.shape
    q = h @ p["wq"]
    k = h @ p["wk"]
    v = h @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def attn_block_full(cfg, p, x, rope_cs, *, impl, causal=True, window=None,
                    q_offset=0):
    """Self-attention sublayer over a full sequence.  Returns (x, (k, v), aux)."""
    from repro.distributed import policy as pol
    h = _apply_norm(cfg, p["ln1"], x)
    q, k, v = _project_qkv(cfg, p["attn"], h)
    if rope_cs is not None:
        cos, sin = rope_cs
        q = Lyr.apply_rope(q, cos, sin)
        k = Lyr.apply_rope(k, cos, sin)
    q, k, v = pol.constrain_qkv(q, k, v)
    att = Lyr.attention(q, k, v, causal=causal, window=window,
                        q_offset=q_offset, impl=impl)
    att = pol.constrain_attn_out(att)
    B, S = x.shape[:2]
    x = x + att.reshape(B, S, -1) @ p["attn"]["wo"]
    x = pol.constrain_hidden(x)
    aux = jnp.zeros((), jnp.float32)
    h2 = _apply_norm(cfg, p["ln2"], x)
    if "moe" in p:
        ff, aux = Lyr.moe_layer(p["moe"], h2, top_k=cfg.moe.top_k,
                                capacity_factor=cfg.moe.capacity_factor,
                                aux_coef=cfg.moe.router_aux_coef)
    else:
        ff = Lyr.mlp(p["mlp"], h2, gated=cfg.gated_mlp)
    x = x + ff
    return x, (k, v), aux


def cross_block_full(cfg, p, x, enc_kv, *, impl):
    """Cross-attention sublayer (whisper decoder)."""
    h = _apply_norm(cfg, p["ln_x"], x)
    B, S, _ = h.shape
    q = (h @ p["xattn"]["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    ck, cv = enc_kv
    att = Lyr.attention(q, ck, cv, causal=False, impl=impl)
    return x + att.reshape(B, S, -1) @ p["xattn"]["wo"]


def _enc_cross_kv(cfg, p, enc_out):
    """K/V of the encoder output under a decoder layer's cross-attn weights."""
    B, S, _ = enc_out.shape
    ck = (enc_out @ p["xattn"]["wk"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    cv = (enc_out @ p["xattn"]["wv"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    return ck, cv


# ---------------------------------------------------------------------------
# embedding / head / loss
# ---------------------------------------------------------------------------

def embed_inputs(cfg, params, inputs):
    """Token (+frontend) embedding.  Returns (B, S_total, D)."""
    x = params["embed"][inputs["tokens"]]
    if cfg.frontend == "vision":
        vis = inputs["vision_embeds"] @ params["vision_proj"]
        x = jnp.concatenate([vis.astype(x.dtype), x], axis=1)
    return x


def lm_head_weights(cfg, params):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def chunked_cross_entropy(cfg, params, hidden, labels, chunk=512):
    """Next-token CE without materialising (B, S, V) logits.

    hidden: (B, S, D); labels: (B, S) int32, -1 = ignore.
    """
    w = lm_head_weights(cfg, params)
    B, S, D = hidden.shape
    chunk = min(chunk, S)
    nc = -(-S // chunk)
    pad = nc * chunk - S
    hp = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
    lp = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    hp = hp.reshape(B, nc, chunk, D).transpose(1, 0, 2, 3)
    lp = lp.reshape(B, nc, chunk).transpose(1, 0, 2)

    def step(carry, blk):
        tot, cnt = carry
        h, lab = blk
        logits = (h @ w).astype(jnp.float32)                 # (B, c, V)
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(lab, 0)[..., None], axis=-1)[..., 0]
        valid = (lab >= 0).astype(jnp.float32)
        tot = tot + jnp.sum((logz - tgt) * valid)
        cnt = cnt + jnp.sum(valid)
        return (tot, cnt), None

    (tot, cnt), _ = jax.lax.scan(step, (0.0, 0.0), (hp, lp))
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# full-sequence forward (shared by train & prefill)
# ---------------------------------------------------------------------------

def _rope_for(cfg, S, offset=0):
    if cfg.family == "audio":
        return None          # whisper: sinusoidal absolute positions
    pos = offset + jnp.arange(S)
    return Lyr.rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta)


def forward_hidden(cfg, params, inputs, *, attn_impl="chunked", window=None,
                   remat=True, collect_kv=False):
    """Embeds + all decoder layers + final norm.

    Returns (hidden (B,S,D), aux_loss, kv_pytree or None).
    kv_pytree (collect_kv=True):
      dense-ish: {'k': (L,B,S,KH,hd), 'v': ...}
      ssm/hybrid/audio: family-specific (see init_cache).
    """
    x = embed_inputs(cfg, params, inputs)
    B, S, _ = x.shape
    rope_cs = _rope_for(cfg, S)
    if cfg.family == "audio":
        x = x + Lyr.sinusoidal_positions(S, cfg.d_model).astype(x.dtype)[None]
    aux = jnp.zeros((), jnp.float32)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(carry, lp):
            x, aux = carry
            x, kv, a = attn_block_full(cfg, lp, x, rope_cs, impl=attn_impl,
                                       window=window)
            return (x, aux + a), kv if collect_kv else None
        body = jax.checkpoint(body) if remat else body
        (x, aux), kvs = jax.lax.scan(body, (x, aux), params["layers"])
        kv_tree = {"k": kvs[0], "v": kvs[1]} if collect_kv else None

    elif cfg.family == "ssm":
        from repro.distributed import policy as pol

        def body(carry, lp):
            x, aux = carry
            h = _apply_norm(cfg, lp["ln"], x)
            y, cache = SSM.mamba1_block(lp["mamba"], h, cfg=cfg)
            x = pol.constrain_hidden(x + y)
            return (x, aux), cache if collect_kv else None
        body = jax.checkpoint(body) if remat else body
        (x, aux), caches = jax.lax.scan(body, (x, aux), params["layers"])
        kv_tree = caches if collect_kv else None

    elif cfg.family == "hybrid":
        from repro.distributed import policy as pol
        x0 = x
        attn_kvs, mamba_caches = [], []

        def mamba_body(x, lp):
            x, cache = hybrid_layer(cfg, lp, x, None)
            return pol.constrain_hidden(x), cache if collect_kv else None
        mbody = jax.checkpoint(mamba_body) if remat else mamba_body

        def attend(q, k, v):
            return Lyr.attention(q, k, v, causal=True, window=window,
                                 impl=attn_impl), (k, v)

        for kind, i, j in hybrid_segments(cfg, 0, cfg.num_layers):
            if kind == "mamba":
                lp = jax.tree.map(lambda a: a[i:j], params["layers"])
                x, caches = jax.lax.scan(mbody, x, lp)
            else:
                t, kv = hybrid_block(cfg, params, j, x, x0, rope_cs, attend)
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                x, cache = hybrid_layer(cfg, lp, x, t)
                x = pol.constrain_hidden(x)
                caches = jax.tree.map(lambda a: a[None], cache)
                attn_kvs.append(kv)
            if collect_kv:
                mamba_caches.append(caches)
        kv_tree = None
        if collect_kv:
            mcat = jax.tree.map(lambda *a: jnp.concatenate(a, 0), *mamba_caches)
            kv_tree = {"mamba": mcat,
                       "attn": {"k": jnp.stack([kv[0] for kv in attn_kvs]),
                                "v": jnp.stack([kv[1] for kv in attn_kvs])}}

    elif cfg.family == "audio":
        enc_out = encode_audio(cfg, params, inputs["frames"],
                               attn_impl=attn_impl, remat=remat)

        def body(carry, lp):
            x, aux = carry
            x, kv, a = attn_block_full(cfg, lp, x, rope_cs, impl=attn_impl,
                                       window=window)
            ckv = _enc_cross_kv(cfg, lp, enc_out)
            x = cross_block_full(cfg, lp, x, ckv, impl=attn_impl)
            outs = (kv, ckv) if collect_kv else None
            return (x, aux + a), outs
        body = jax.checkpoint(body) if remat else body
        (x, aux), outs = jax.lax.scan(body, (x, aux), params["layers"])
        kv_tree = None
        if collect_kv:
            (kvs, ckvs) = outs
            kv_tree = {"k": kvs[0], "v": kvs[1],
                       "ck": ckvs[0], "cv": ckvs[1]}
    else:
        raise ValueError(cfg.family)

    x = _apply_norm(cfg, params["final_norm"], x)
    return x, aux, kv_tree


def encode_audio(cfg, params, frames, *, attn_impl="chunked", remat=True):
    """Whisper encoder over stub frame embeddings (B, T_enc, D)."""
    x = frames + Lyr.sinusoidal_positions(
        frames.shape[1], cfg.d_model).astype(frames.dtype)[None]

    def body(carry, lp):
        x, aux = carry
        x, _, a = attn_block_full(cfg, lp, x, None, impl=attn_impl,
                                  causal=False)
        return (x, aux + a), None
    body = jax.checkpoint(body) if remat else body
    (x, _), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                             params["encoder"]["layers"])
    return _apply_norm(cfg, params["encoder"]["final_norm"], x)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def train_loss(cfg, params, batch, *, attn_impl="chunked", remat=True):
    """batch: {'tokens', 'labels', [frontend inputs]} -> (loss, aux_metrics)."""
    hidden, aux, _ = forward_hidden(cfg, params, batch, attn_impl=attn_impl,
                                    window=cfg.sliding_window, remat=remat)
    labels = batch["labels"]
    if cfg.frontend == "vision":
        nf = batch["vision_embeds"].shape[1]
        ignore = jnp.full(labels.shape[:1] + (nf,), -1, labels.dtype)
        labels = jnp.concatenate([ignore, labels], axis=1)
    ce = chunked_cross_entropy(cfg, params, hidden, labels)
    return ce + aux, {"ce": ce, "aux": aux}


def prefill(cfg, params, inputs, *, max_seq, attn_impl="chunked", window=None,
            remat=True):
    """Full-prompt forward.  Returns (last_logits (B,V), cache)."""
    window = window if window is not None else cfg.sliding_window
    hidden, _, kv = forward_hidden(cfg, params, inputs, attn_impl=attn_impl,
                                   window=window, remat=remat, collect_kv=True)
    B, S, _ = hidden.shape
    logits = (hidden[:, -1] @ lm_head_weights(cfg, params)).astype(jnp.float32)
    cache = _cache_from_prefill(cfg, kv, S, max_seq, window)
    return logits, cache


def _cache_from_prefill(cfg, kv, S, max_seq, window):
    pos = jnp.asarray(S, jnp.int32)
    cache_len = _cache_len(cfg, max_seq, window)

    def fit_seq(a):
        # a: (L, B, S, KH, hd) -> HEADS-MAJOR (L, B, KH, cache_len, hd);
        # one transpose at prefill time buys transpose-free decode steps.
        if a.shape[2] >= cache_len:
            a = a[:, :, a.shape[2] - cache_len:]
        else:
            padw = [(0, 0)] * a.ndim
            padw[2] = (0, cache_len - a.shape[2])
            a = jnp.pad(a, padw)
        return a.transpose(0, 1, 3, 2, 4)

    if cfg.family in ("dense", "moe", "vlm"):
        return {"k": fit_seq(kv["k"]), "v": fit_seq(kv["v"]), "pos": pos}
    if cfg.family == "ssm":
        return {"mamba": kv, "pos": pos}
    if cfg.family == "hybrid":
        return {"mamba": kv["mamba"],
                "attn": {"k": fit_seq(kv["attn"]["k"]),
                         "v": fit_seq(kv["attn"]["v"])},
                "pos": pos}
    if cfg.family == "audio":
        return {"k": fit_seq(kv["k"]), "v": fit_seq(kv["v"]),
                "ck": kv["ck"].transpose(0, 1, 3, 2, 4),
                "cv": kv["cv"].transpose(0, 1, 3, 2, 4), "pos": pos}
    raise ValueError(cfg.family)


def _cache_len(cfg, max_seq, window):
    return min(max_seq, window) if window else max_seq


def effective_window(cfg, seq_len):
    """Attention window used at this sequence length (swa-variant policy)."""
    if cfg.sliding_window:
        return cfg.sliding_window
    if cfg.long_context_window and seq_len > 131_072:
        return cfg.long_context_window
    return None


def init_cache(cfg, batch, max_seq, dtype=jnp.float32, window=None):
    """Zero-initialised decode cache (shapes mirror _cache_from_prefill)."""
    L, KH, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    cl = _cache_len(cfg, max_seq, window)
    pos = jnp.zeros((), jnp.int32)
    if cfg.family in ("dense", "moe", "vlm"):
        kv = jnp.zeros((L, batch, KH, cl, hd), dtype)
        return {"k": kv, "v": kv, "pos": pos}
    if cfg.family == "ssm":
        s = cfg.ssm
        return {"mamba": {"conv": jnp.zeros((L, batch, s.d_conv - 1, cfg.d_inner), dtype),
                          "ssm": jnp.zeros((L, batch, cfg.d_inner, s.d_state), jnp.float32)},
                "pos": pos}
    if cfg.family == "hybrid":
        s = cfg.ssm
        H = cfg.d_inner // s.head_dim
        n_apps = len(cfg.app_layers)
        conv_dim = cfg.d_inner + 2 * s.n_groups * s.d_state
        kv = jnp.zeros((n_apps, batch, KH, cl, hd), dtype)
        return {"mamba": {"conv": jnp.zeros((L, batch, s.d_conv - 1, conv_dim), dtype),
                          "ssm": jnp.zeros((L, batch, H, s.head_dim, s.d_state), jnp.float32)},
                "attn": {"k": kv, "v": kv},
                "pos": pos}
    if cfg.family == "audio":
        kv = jnp.zeros((L, batch, KH, cl, hd), dtype)
        enc = cfg.encoder.context_len
        ckv = jnp.zeros((L, batch, KH, enc, hd), dtype)
        return {"k": kv, "v": kv, "ck": ckv, "cv": ckv, "pos": pos}
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _ring_attend(q, k, v, k_all, v_all, li, pos, *, impl):
    """Write one token's K/V into layer ``li`` of the STACKED heads-major
    ring cache (L, B, KH, CL, hd) and attend against it.

    The caches stay scan CARRIES and only the (1, B, KH, 1, hd) token slice
    is written — returning per-layer caches as scan ys makes XLA copy the
    whole layer cache every step (measured 2x67 MB/layer/device on
    yi-34b decode_32k, 32x the roofline minimum).
    Returns (att, k_all, v_all)."""
    CL = k_all.shape[3]
    widx = jnp.mod(pos, CL)                       # ring write index
    li = jnp.asarray(li, jnp.int32)
    k_t = k.transpose(0, 2, 1, 3)                 # (B, KH, 1, hd)
    v_t = v.transpose(0, 2, 1, 3)
    # two-step ring write: slice the layer cache, token-DUS into it, write
    # the slice back at a NON-sharded dim (dim 0).  A direct 5-dim DUS with
    # the dynamic widx makes GSPMD select over the WHOLE stacked cache per
    # layer (measured 8 GB/layer/device); this bounds it to one layer.
    k_layer = jax.lax.dynamic_index_in_dim(k_all, li, 0, keepdims=False)
    v_layer = jax.lax.dynamic_index_in_dim(v_all, li, 0, keepdims=False)
    k_layer = jax.lax.dynamic_update_slice(
        k_layer, k_t.astype(k_layer.dtype), (0, 0, widx, 0))
    v_layer = jax.lax.dynamic_update_slice(
        v_layer, v_t.astype(v_layer.dtype), (0, 0, widx, 0))
    k_all = jax.lax.dynamic_update_index_in_dim(k_all, k_layer, li, 0)
    v_all = jax.lax.dynamic_update_index_in_dim(v_all, v_layer, li, 0)
    # Ring-buffer semantics: the cache length CL is already min(max_seq,
    # window), so windowing is enforced by eviction; mask only invalid slots.
    eff_pos = jnp.minimum(pos + 1, CL)
    if impl == "pallas":
        from repro.kernels import ops as kops
        att = kops.flash_decode_attention(q, k_layer, v_layer, eff_pos)
    else:
        att = Lyr.decode_attention(q, k_layer, v_layer, pos=eff_pos,
                                   window=None)
    return att, k_all, v_all


def _attn_decode_sublayer(cfg, p, x, k_all, v_all, li, pos, *, window,
                          impl="chunked"):
    """One-token self-attn against the STACKED heads-major cache.

    k/v_all: (L, B, KH, CL, hd); li: layer index (traced or static)."""
    B = x.shape[0]
    h = _apply_norm(cfg, p["ln1"], x)
    q, k, v = _project_qkv(cfg, p["attn"], h)
    cos, sin = Lyr.rope_cos_sin(pos[None], cfg.head_dim, cfg.rope_theta) \
        if cfg.family != "audio" else (None, None)
    if cos is not None:
        q = Lyr.apply_rope(q, cos[None], sin[None])
        k = Lyr.apply_rope(k, cos[None], sin[None])
    att, k_all, v_all = _ring_attend(q, k, v, k_all, v_all, li, pos,
                                     impl=impl)
    x = x + att.reshape(B, 1, -1) @ p["attn"]["wo"]
    h2 = _apply_norm(cfg, p["ln2"], x)
    if "moe" in p:
        ff, _ = Lyr.moe_layer(p["moe"], h2, top_k=cfg.moe.top_k,
                              capacity_factor=cfg.moe.capacity_factor)
    else:
        ff = Lyr.mlp(p["mlp"], h2, gated=cfg.gated_mlp)
    return x + ff, k_all, v_all


def decode_step(cfg, params, token, cache, *, window=None, attn_impl="chunked"):
    """token: (B, 1) int32.  Returns (logits (B, V) fp32, new_cache)."""
    x = params["embed"][token]
    pos = cache["pos"]
    if cfg.family == "audio":
        x = x + Lyr.sinusoidal_at(pos[None], cfg.d_model).astype(x.dtype)[None]

    if cfg.family in ("dense", "moe", "vlm"):
        def body(carry, lp):
            x, k_all, v_all, li = carry
            x, k_all, v_all = _attn_decode_sublayer(
                cfg, lp, x, k_all, v_all, li, pos, window=window,
                impl=attn_impl)
            return (x, k_all, v_all, li + 1), None
        (x, kcs, vcs, _), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"], jnp.int32(0)),
            params["layers"])
        new_cache = {"k": kcs, "v": vcs, "pos": pos + 1}

    elif cfg.family == "ssm":
        def body(x, xs):
            lp, conv, hssm = xs
            h = _apply_norm(cfg, lp["ln"], x)
            y, nc = SSM.mamba1_block(lp["mamba"], h,
                                     cache={"conv": conv, "ssm": hssm}, cfg=cfg)
            return x + y, (nc["conv"], nc["ssm"])
        x, (convs, hs) = jax.lax.scan(
            body, x, (params["layers"], cache["mamba"]["conv"],
                      cache["mamba"]["ssm"]))
        new_cache = {"mamba": {"conv": convs, "ssm": hs}, "pos": pos + 1}

    elif cfg.family == "hybrid":
        x0 = x
        rope_cs = Lyr.rope_cos_sin(pos[None], cfg.head_dim, cfg.rope_theta)
        conv_all, ssm_all = cache["mamba"]["conv"], cache["mamba"]["ssm"]
        k_all, v_all = cache["attn"]["k"], cache["attn"]["v"]

        def mbody(x, xs):
            lp, conv, hssm = xs
            x, nc = hybrid_layer(cfg, lp, x, None,
                                 {"conv": conv, "ssm": hssm})
            return x, (nc["conv"], nc["ssm"])

        convs_out, hs_out = [], []
        for kind, i, j in hybrid_segments(cfg, 0, cfg.num_layers):
            if kind == "mamba":
                lp = jax.tree.map(lambda a: a[i:j], params["layers"])
                x, (nconv, nh) = jax.lax.scan(
                    mbody, x, (lp, conv_all[i:j], ssm_all[i:j]))
            else:
                def attend(q, k, v, g=j):
                    nonlocal k_all, v_all
                    att, k_all, v_all = _ring_attend(
                        q, k, v, k_all, v_all, g, pos, impl=attn_impl)
                    return att, None
                t, _ = hybrid_block(cfg, params, j, x, x0, rope_cs, attend)
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                x, nc = hybrid_layer(cfg, lp, x, t,
                                     {"conv": conv_all[i], "ssm": ssm_all[i]})
                nconv, nh = nc["conv"][None], nc["ssm"][None]
            convs_out.append(nconv)
            hs_out.append(nh)
        new_cache = {
            "mamba": {"conv": jnp.concatenate(convs_out, 0),
                      "ssm": jnp.concatenate(hs_out, 0)},
            "attn": {"k": k_all, "v": v_all},
            "pos": pos + 1}

    elif cfg.family == "audio":
        def body(carry, xs):
            x, k_all, v_all, li = carry
            lp, ck, cv = xs              # cross k/v are read-only xs
            x, k_all, v_all = _attn_decode_sublayer(
                cfg, lp, x, k_all, v_all, li, pos, window=window,
                impl=attn_impl)
            xq = (_apply_norm(cfg, lp["ln_x"], x) @ lp["xattn"]["wq"]).reshape(
                x.shape[0], 1, cfg.num_heads, cfg.head_dim)
            att = Lyr.decode_attention(xq, ck, cv, pos=ck.shape[2])
            x = x + att.reshape(x.shape[0], 1, -1) @ lp["xattn"]["wo"]
            return (x, k_all, v_all, li + 1), None
        (x, kcs, vcs, _), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"], jnp.int32(0)),
            (params["layers"], cache["ck"], cache["cv"]))
        new_cache = {"k": kcs, "v": vcs, "ck": cache["ck"], "cv": cache["cv"],
                     "pos": pos + 1}
    else:
        raise ValueError(cfg.family)

    x = _apply_norm(cfg, params["final_norm"], x)
    logits = (x[:, 0] @ lm_head_weights(cfg, params)).astype(jnp.float32)
    return logits, new_cache
