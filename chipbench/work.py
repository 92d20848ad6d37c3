"""Operations and bytes the algorithms need, counted from the configuration.

Nothing here asks the compiler (``cost_analysis``) what an implementation
does: the counts are of the algorithm, so a later change of kernel or
cache layout is measured against the same work.  A multiply-add is two
operations.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak row of ``device_kind``; an unknown device is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add its published numbers")
    return table[device_kind]


def _dims(cfg: dict):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, H, cfg["num_key_value_heads"], d // H, cfg["intermediate_size"]


def lm_matmul_flops_per_token(cfg: dict) -> int:
    """Projections and MLP of every layer plus the LM head, per token."""
    d, H, KH, hd, F = _dims(cfg)
    layer = d * H * hd + 2 * d * KH * hd + H * hd * d + 3 * d * F
    return 2 * (cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"])


def lm_attn_flops(cfg: dict, ctx: int) -> int:
    """Scores and weighted values of one token over ``ctx`` positions, all
    layers."""
    d, H, KH, hd, F = _dims(cfg)
    return cfg["num_hidden_layers"] * 4 * H * hd * ctx


def decode_step_flops(cfg: dict, ctxs) -> int:
    """One decode step of the live slots; ``ctxs`` are their context
    lengths including the token being decoded."""
    return sum(lm_matmul_flops_per_token(cfg) + lm_attn_flops(cfg, c)
               for c in ctxs)


def flash_decode_work(cfg: dict, ctxs, *, kv_bytes: int,
                      q_bytes: int = 4) -> tuple:
    """``(flops, bytes)`` of one decode-attention call (one layer, the
    whole batch): the live K and V of every live slot at the cache's
    dtype, plus its query and output rows."""
    d, H, KH, hd, F = _dims(cfg)
    flops = sum(4 * H * hd * c for c in ctxs)
    nbytes = sum(2 * KH * c * hd * kv_bytes + 2 * H * hd * q_bytes
                 for c in ctxs)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict) -> tuple:
    """``(seconds, bound)``: the larger of compute and memory time at the
    chip's peaks, and which of the two it is."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
