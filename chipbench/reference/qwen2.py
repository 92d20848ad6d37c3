"""Plain Qwen2 forward pass (the Qwen2/Qwen2.5 model card and config).

Pre-norm decoder: RMSNorm (variance in float32), GQA attention with
bias on Q, K and V, rotary embeddings on the two halves of each head
(theta from the configuration), causal softmax, SwiGLU MLP, final
RMSNorm and an LM head tied to the embedding.  One jitted layer at a
time over a batch of whole sequences, so that the reference fits beside
nothing but its own weights.

``dtype`` and ``precision`` select the arithmetic: float32 at "highest"
is the reference; bfloat16 weights and activations at the default
precision is the control, the next precision below what the served
configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """Rotate the two halves of each head by position (x: B, S, H, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, hd/2)
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           -1).astype(x.dtype)


def _layer(cfg, precision, p, x):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    KH, hd = cfg["num_key_value_heads"], d // H
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    mm = functools.partial(jnp.matmul, precision=precision)
    B, S, _ = x.shape
    a = p["attn"]
    h = _rms(x, p["ln1"]["scale"], eps)
    q = (mm(h, a["wq"]) + a["bq"]).reshape(B, S, H, hd)
    k = (mm(h, a["wk"]) + a["bk"]).reshape(B, S, KH, hd)
    v = (mm(h, a["wv"]) + a["bv"]).reshape(B, S, KH, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, H // KH, axis=2)          # query head j -> kv j // g
    v = jnp.repeat(v, H // KH, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=precision,
                   preferred_element_type=jnp.float32) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v,
                   precision=precision).reshape(B, S, H * hd)
    x = x + mm(o, a["wo"])
    h2 = _rms(x, p["ln2"]["scale"], eps)
    m = p["mlp"]
    return x + mm(jax.nn.silu(mm(h2, m["w_gate"])) * mm(h2, m["w_up"]),
                  m["w_down"])


def hidden(cfg: dict, params: dict, tokens, *, dtype=jnp.float32,
           precision="highest"):
    """Final-normed hidden states ``(B, S, d)`` of ``tokens`` ``(B, S)``."""
    layer = jax.jit(functools.partial(_layer, cfg, precision))
    cast = jax.jit(lambda t: jax.tree.map(lambda a: a.astype(dtype), t))
    x = cast(params["embed"])[jnp.asarray(tokens)]
    for i in range(cfg["num_hidden_layers"]):
        lp = cast(jax.tree.map(lambda a: a[i], params["layers"]))
        x = layer(lp, x)
    return _rms(x, params["final_norm"]["scale"].astype(dtype),
                cfg["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("precision",))
def _score(h, head, targets, *, precision):
    """Per position: the best logit, the logit of ``targets`` and the
    argmax, for one sequence's hidden states ``h`` ``(S, d)``."""
    lg = jnp.matmul(h, head.T, precision=precision,
                    preferred_element_type=jnp.float32)
    at = jnp.take_along_axis(lg, targets[:, None], -1)[:, 0]
    return lg.max(-1), at, lg.argmax(-1).astype(jnp.int32)


def gaps(cfg: dict, params: dict, seqs: list, prompt_lens: list, *,
         control: bool = True) -> dict:
    """Gaps below the reference's best logit, at every served position.

    ``seqs[i]`` is a prompt of ``prompt_lens[i]`` tokens followed by the
    tokens the system served.  The served token at position ``t`` was
    chosen from the logits at ``t - 1``; its gap is the float32
    reference's best logit there minus the reference's logit of that
    token (0 where they agree).  With ``control``, the same is read for
    the token that the bfloat16 reference puts first at each position.
    Returns ``{"served": [array per seq], "control": [...]}``."""
    S = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s                  # causal: the pad never reaches
    h32 = hidden(cfg, params, toks)
    hbf = hidden(cfg, params, toks, dtype=jnp.bfloat16,
                 precision="default") if control else None
    head32 = params["embed"]
    headbf = head32.astype(jnp.bfloat16) if control else None
    out = {"served": [], "control": [], "control_first": []}
    for i, (s, p0) in enumerate(zip(seqs, prompt_lens)):
        n = len(s)
        tgt = np.zeros(S, np.int32)
        tgt[:n - 1] = s[1:]
        best, at, _ = _score(h32[i], head32, jnp.asarray(tgt),
                             precision="highest")
        best, at = np.asarray(best), np.asarray(at)
        pos = np.arange(p0 - 1, n - 1)
        out["served"].append(best[pos] - at[pos])
        if control:
            _, _, am = _score(hbf[i], headbf, jnp.asarray(tgt),
                              precision="default")
            _, at_c, _ = _score(h32[i], head32, am, precision="highest")
            out["control"].append(best[pos] - np.asarray(at_c)[pos])
            out["control_first"].append(np.asarray(am)[pos])
    return out
