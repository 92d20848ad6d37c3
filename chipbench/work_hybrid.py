"""Operations and bytes of the Zamba2-style hybrid, counted from the
configuration file (the algorithm's work, as ``work.py`` counts it; a
multiply-add is two operations).

Per decoded token: every Mamba-2 layer's in_proj and out_proj, the
selective recurrence, each application's shared-block projections
(q, k, v from 2 x hidden, o_proj to hidden), gated MLP, LoRA adapter and
output linear, attention over the application's context, and the tied
LM head.
"""
from __future__ import annotations


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    H, P = di // cfg["mamba_headdim"], cfg["mamba_headdim"]
    return d, di, H, P, cfg["mamba_ngroups"], cfg["mamba_d_state"]


def n_apps(cfg: dict) -> int:
    return sum(i < cfg["num_hidden_layers"] for i in cfg["hybrid_layer_ids"])


def ssd_flops_per_token(cfg: dict) -> int:
    """The recurrence of one layer for one token: decay and update of
    every head's (P, N) state, and its read-out."""
    d, di, H, P, G, N = _dims(cfg)
    return 6 * H * P * N


def matmul_flops_per_token(cfg: dict) -> int:
    d, di, H, P, G, N = _dims(cfg)
    F, r = cfg["intermediate_size"], cfg["adapter_rank"]
    w = cfg["num_attention_heads"] * cfg["attention_head_dim"]
    mamba = d * (2 * di + 2 * G * N + H) + di * d
    app = 3 * 2 * d * w + w * d + 3 * d * F + d * r + r * 2 * F + d * d
    return 2 * (cfg["num_hidden_layers"] * mamba + n_apps(cfg) * app
                + d * cfg["vocab_size"])


def attn_flops(cfg: dict, ctx: int) -> int:
    """Scores and weighted values of one token over ``ctx`` positions,
    every application."""
    w = cfg["num_attention_heads"] * cfg["attention_head_dim"]
    return n_apps(cfg) * 4 * w * ctx


def decode_step_flops(cfg: dict, ctxs) -> int:
    """One decode step of the live slots; ``ctxs`` are their context
    lengths including the token being decoded."""
    per = matmul_flops_per_token(cfg) \
        + cfg["num_hidden_layers"] * ssd_flops_per_token(cfg)
    return sum(per + attn_flops(cfg, c) for c in ctxs)


def flash_decode_work(cfg: dict, ctxs, *, kv_bytes: int,
                      q_bytes: int = 4) -> tuple:
    """``(flops, bytes)`` of one decode-attention call (one application,
    the whole batch): the live K and V of every live slot (MHA: one KV
    head per query head) at the cache's dtype, plus query and output."""
    H, hd = cfg["num_attention_heads"], cfg["attention_head_dim"]
    flops = sum(4 * H * hd * c for c in ctxs)
    nbytes = sum(2 * H * c * hd * kv_bytes + 2 * H * hd * q_bytes
                 for c in ctxs)
    return flops, nbytes


def ssd_scan_work(cfg: dict, rows: int, tokens: int) -> tuple:
    """``(flops, bytes)`` of one SSD scan call over ``rows`` sequences of
    ``tokens`` positions each (one layer): the recurrence's operations;
    the inputs x and dt, each group's B and C, the output y, and the
    state read and written, in float32."""
    d, di, H, P, G, N = _dims(cfg)
    flops = rows * tokens * ssd_flops_per_token(cfg)
    nbytes = 4 * rows * (tokens * (2 * H * P + H + 2 * G * N)
                         + 2 * H * P * N)
    return flops, nbytes
