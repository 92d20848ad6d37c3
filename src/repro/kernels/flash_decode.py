"""Pallas TPU flash-decode attention: one query token vs a long KV cache.

Motivation (EXPERIMENTS.md, hillclimb pair A): after the sharding/layout
fixes, yi-34b decode_32k is left ~3x above its roofline floor because the
XLA fallback reads the cache through separate mask/softmax/PV ops.  This
kernel streams the HEADS-MAJOR cache (B, KH, S, D) through VMEM once,
keeping the (G, 1)/(G, D) online-softmax state in scratch — the cache is
touched exactly once per step, which IS the decode roofline.

Grid: (B, KH, num_kv_blocks); the kv-block axis is innermost (sequential on
TPU), so scratch persists across it.  The GQA group dim G rides inside the
block as the "rows" of a (G, block_k) score tile.  Invalid ring slots
(kpos >= pos) are masked via a scalar `pos` operand in SMEM.

Validated against ref.decode_attention_ref in interpret mode (tests/).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# smallest block the grid is still worth carving at; below this a pad
# copy beats the tiny-block launch overhead
_MIN_BLOCK_K = 16


def _pick_block_k(S: int, block_k: int) -> int:
    """Largest block size <= ``block_k`` that divides ``S``.

    A non-dividing block forces ``jnp.pad`` of the WHOLE cache — an
    O(cache) copy on every decode step, which defeats the point of a
    cache-streamed kernel.  Runner caches are power-of-two ``max_seq``,
    so the hot path always finds an exact divisor; only near-prime S
    (divisors all < ``_MIN_BLOCK_K``) falls back to padding."""
    block_k = min(block_k, S)
    if S % block_k:
        div = next((d for d in range(block_k, _MIN_BLOCK_K - 1, -1)
                    if S % d == 0), None)
        if div is not None:
            block_k = div
    return block_k


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, block_k, num_blocks, seq, per_row):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # per_row is a trace-time Python bool: the shared-pos program is
    # byte-identical to the pre-slot-pool kernel, the ragged program
    # indexes this batch row's own valid prefix from SMEM
    pos = pos_ref[pl.program_id(0)] if per_row else pos_ref[0]
    k_start = kj * block_k

    @pl.when(k_start < pos)       # skip blocks past the valid prefix
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (block_k, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / np.sqrt(q.shape[-1]))         # (G, block_k)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = jnp.logical_and(kpos < pos, kpos < seq)
        s = jnp.where(ok, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(kj == num_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_attention(q, k_cache, v_cache, *, pos, block_k=512,
                           interpret=None):
    """q: (B, 1, H, D); k/v_cache HEADS-MAJOR (B, KH, S, D); pos: count of
    valid entries — a scalar shared by the whole batch, or a ``(B,)``
    vector for ragged slot pools (each row masks its own prefix; rows
    with pos 0 attend to nothing and produce zeros).  Returns
    (B, 1, H, D)."""
    B, _, H, D = q.shape
    KH, S = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    if interpret is None:
        # nk: allow[NK03]: per-backend constant is deliberate (interpret on CPU)
        interpret = jax.default_backend() == "cpu"
    block_k = _pick_block_k(S, block_k)
    nb = -(-S // block_k)
    pad = nb * block_k - S
    kp, vp = k_cache, v_cache
    if pad:     # degenerate S only (near-prime): see _pick_block_k
        kp = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qg = q.reshape(B, KH, G, D)
    # shared pos stays a (1,) SMEM scalar (the historic program); a (B,)
    # vector keeps one entry per batch row and flips the kernel into
    # per-row masking.  A size-1 vector is folded onto the scalar path so
    # slot-count-1 pools run the single-session kernel.
    per_row = jnp.ndim(pos) == 1 and pos.shape[0] > 1
    if per_row:
        pos_arr = pos.astype(jnp.int32).reshape(B)
    else:
        pos_arr = jnp.full((1,), pos, jnp.int32) if jnp.ndim(pos) == 0 \
            else pos.astype(jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, block_k=block_k,
                               num_blocks=nb, seq=S, per_row=per_row)
    out = pl.pallas_call(
        kernel,
        grid=(B, KH, nb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(pos_arr, qg, kp, vp)
    return out.reshape(B, 1, H, D)
