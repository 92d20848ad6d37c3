"""Plain Zamba2 forward pass (Zyphra/Zamba2-7B-Instruct config.json, the
Zamba2 paper arXiv:2411.15242, and the Hugging Face ``modeling_zamba2``
layer equations).

The model: ``num_hidden_layers`` Mamba-2 layers.  Before each layer of
``hybrid_layer_ids`` a shared transformer block is applied; application
``g`` uses block ``g mod num_mem_blocks`` (A B A B ...), its own LoRA
adapter on the MLP's gate_up projection and its own output ``linear``:

    T = linear_g(MLP_g(norm_ff(Attn(norm_in(concat(x, x0))))))
    x = x + Mamba(norm(x + T))          (no application: T = 0)

``x0`` is the token embedding.  Attention is multi-head over
``attention_hidden_size`` = 2 x hidden inputs, ``attention_head_dim``
wide heads, rotary embeddings (``use_mem_rope``, theta ``rope_theta``)
on the two halves of each head, causal softmax over
q.k / sqrt(head_dim / 2) (Zamba2's scale), no bias, o_proj to hidden.
The MLP is gelu(gate) * up with gate|up = h @ gate_up + (h @ A) @ B.
Mamba-2: in_proj to z | x B C | dt, causal depthwise conv (with bias)
and SiLU over x B C, dt = softplus(dt + dt_bias), A = -exp(A_log), the
selective recurrence per head over its group's B and C
(``mamba_ngroups`` groups of consecutive heads), the D skip, RMSNorm of
y * silu(z) over each group's channels separately, out_proj.  Final
RMSNorm and an LM head tied to the embedding.

The recurrence runs one token at a time (``lax.scan`` over positions),
not in the chunked SSD form the program's kernel uses; there is no
cache, no kernel and no batching of sessions beyond padding.  One jitted
layer at a time over a batch of whole sequences, so that the reference
fits beside nothing but its own weights.

Departures from the published description, each an assumption:
- ``tie_word_embeddings`` is not in the catalog's config; the LM head is
  taken tied to the embedding (the Zamba2 default).
- Weights are random from the benchmark's seed, in the program's tree
  layout (``chipbench/weights_hybrid.py``): gate_up as one matrix
  (gate first), the adapter as (down, up), the shared blocks stacked on
  a leading block axis, the applications' parameters on an application
  axis.
- The rope covers the whole head (224), as the Hugging Face rotary
  embedding built from ``attention_head_dim`` does.
- The gated norm's epsilon is ``rms_norm_eps``, which equals the 1e-5
  the Hugging Face mixer hard-codes.

``dtype`` and ``precision`` select the arithmetic: float32 at "highest"
is the reference; bfloat16 weights and activations at the default
precision is the control, the next precision below what the served
configuration states (the recurrence's state accumulates in float32 in
both, as Mamba kernels keep it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.qwen2 import _rms, _rope, _score

CHUNK = 8           # sequences per reference pass


def _dims(cfg):
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    H, P = di // cfg["mamba_headdim"], cfg["mamba_headdim"]
    return d, di, H, P, cfg["mamba_ngroups"], cfg["mamba_d_state"]


def app_layers(cfg) -> list:
    """The layers that an application precedes, below the served depth."""
    return [i for i in cfg["hybrid_layer_ids"]
            if i < cfg["num_hidden_layers"]]


def _mamba(cfg, precision, p, x, t):
    d, di, H, P, G, N = _dims(cfg)
    eps = cfg["rms_norm_eps"]
    mm = functools.partial(jnp.matmul, precision=precision)
    B, S, _ = x.shape
    m = p["mamba"]
    h = _rms(x if t is None else x + t, p["ln"]["scale"], eps)
    zxbcdt = mm(h, m["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], axis=-1)
    K = m["conv_w"].shape[0]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(pad[:, k:k + S].astype(jnp.float32)
               * m["conv_w"][k].astype(jnp.float32) for k in range(K))
    xbc = jax.nn.silu(conv + m["conv_b"].astype(jnp.float32))
    xs, Bc, Cc = jnp.split(xbc, [di, di + G * N], axis=-1)
    xs = xs.reshape(B, S, H, P)
    Bh = jnp.repeat(Bc.reshape(B, S, G, N), H // G, axis=2)   # (B,S,H,N)
    Ch = jnp.repeat(Cc.reshape(B, S, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + m["dt_bias"].astype(jnp.float32))   # (B,S,H)
    A = -jnp.exp(m["A_log"].astype(jnp.float32))

    def step(state, u):
        dt_t, x_t, b_t, c_t = u
        state = jnp.exp(dt_t * A)[:, :, None, None] * state \
            + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision="highest")

    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), jnp.float32),
                        (dt.transpose(1, 0, 2), xs.transpose(1, 0, 2, 3),
                         Bh.transpose(1, 0, 2, 3), Ch.transpose(1, 0, 2, 3)))
    y = y.transpose(1, 0, 2, 3) + xs * m["D"].astype(jnp.float32)[:, None]
    y = y.reshape(B, S, G, di // G) \
        * jax.nn.silu(z.astype(jnp.float32)).reshape(B, S, G, di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    y = y.reshape(B, S, di).astype(x.dtype) * m["norm"]
    return x + mm(y, m["out_proj"])


def _shared(cfg, precision, p, a, x, x0):
    H, hd = cfg["num_attention_heads"], cfg["attention_head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    mm = functools.partial(jnp.matmul, precision=precision)
    B, S, _ = x.shape
    h = _rms(jnp.concatenate([x, x0], -1), p["ln1"]["scale"], eps)
    q = _rope(mm(h, p["attn"]["wq"]).reshape(B, S, H, hd), theta)
    k = _rope(mm(h, p["attn"]["wk"]).reshape(B, S, H, hd), theta)
    v = mm(h, p["attn"]["wv"]).reshape(B, S, H, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=precision,
                   preferred_element_type=jnp.float32) / np.sqrt(hd / 2)
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v,
                   precision=precision).reshape(B, S, H * hd)
    h2 = _rms(mm(o, p["attn"]["wo"]), p["ln2"]["scale"], eps)
    gu = mm(h2, p["mlp"]["w_gate_up"]) \
        + mm(mm(h2, a["adapter_down"]), a["adapter_up"])
    gate, up = jnp.split(gu, 2, axis=-1)
    y = mm(jax.nn.gelu(gate.astype(jnp.float32), approximate=False)
           .astype(x.dtype) * up, p["mlp"]["w_down"])
    return mm(y, a["linear"])


def hidden(cfg: dict, params: dict, tokens, *, dtype=jnp.float32,
           precision="highest"):
    """Final-normed hidden states ``(B, S, d)`` of ``tokens`` ``(B, S)``."""
    mamba = jax.jit(functools.partial(_mamba, cfg, precision))
    shared = jax.jit(functools.partial(_shared, cfg, precision))
    cast = jax.jit(lambda t: jax.tree.map(lambda a: a.astype(dtype), t))
    x = cast(params["embed"])[jnp.asarray(tokens)]
    x0 = x
    apps = app_layers(cfg)
    for i in range(cfg["num_hidden_layers"]):
        t = None
        if i in apps:
            g = apps.index(i)
            bp = cast(jax.tree.map(lambda a: a[g % cfg["num_mem_blocks"]],
                                   params["shared"]))
            ap = cast(jax.tree.map(lambda a: a[g], params["apps"]))
            t = shared(bp, ap, x, x0)
        lp = cast(jax.tree.map(lambda a: a[i], params["layers"]))
        x = mamba(lp, x, t)
    return _rms(x, params["final_norm"]["scale"].astype(dtype),
                cfg["rms_norm_eps"])


def gaps(cfg: dict, params: dict, seqs: list, prompt_lens: list, *,
         control: bool = True) -> dict:
    """Gaps below the reference's best logit, at every served position,
    with the contract of ``reference.qwen2.gaps``: ``seqs[i]`` is a
    prompt of ``prompt_lens[i]`` tokens followed by the served tokens;
    the gap of the token served at ``t`` is the float32 reference's best
    logit at ``t - 1`` minus its logit of that token.  With ``control``,
    the same is read for the token the bfloat16 reference puts first.
    Sequences go through the reference ``CHUNK`` at a time."""
    S = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s                  # causal: the pad never reaches
    head32 = params["embed"]
    headbf = head32.astype(jnp.bfloat16) if control else None
    out = {"served": [], "control": [], "control_first": []}
    for c0 in range(0, len(seqs), CHUNK):
        h32 = hidden(cfg, params, toks[c0:c0 + CHUNK])
        hbf = hidden(cfg, params, toks[c0:c0 + CHUNK], dtype=jnp.bfloat16,
                     precision="default") if control else None
        for j, (s, p0) in enumerate(zip(seqs[c0:c0 + CHUNK],
                                        prompt_lens[c0:c0 + CHUNK])):
            n = len(s)
            tgt = np.zeros(S, np.int32)
            tgt[:n - 1] = s[1:]
            best, at, _ = _score(h32[j], head32, jnp.asarray(tgt),
                                 precision="highest")
            best, at = np.asarray(best), np.asarray(at)
            pos = np.arange(p0 - 1, n - 1)
            out["served"].append(best[pos] - at[pos])
            if control:
                _, _, am = _score(hbf[j], headbf, jnp.asarray(tgt),
                                  precision="default")
                _, at_c, _ = _score(h32[j], head32, am, precision="highest")
                out["control"].append(best[pos] - np.asarray(at_c)[pos])
                out["control_first"].append(np.asarray(am)[pos])
        del h32, hbf
    return out
