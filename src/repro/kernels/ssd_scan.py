"""Pallas TPU chunked SSD scan (Mamba-2) in MATMUL form.

This is the genuinely TPU-native adaptation of the selective scan: where the
CUDA kernel streams timesteps per thread, the SSD formulation turns a chunk
into three MXU matmuls (Dao & Gu 2024), which is exactly what the 128x128
systolic array wants:

  within a chunk (alpha_t = exp(cumsum(dt*A))):
    y = [ (C B^T) (.) decay-ratio (.) dt ]_tril @ x   +  alpha * (C @ h0^T)
    h' = alpha_L * h0 + x^T @ (B (.) (alpha_L/alpha) dt)

All decay ratios are <= 1 (A < 0), so the form is numerically stable.  The
recurrent state h (P, N) stays in VMEM scratch across the sequential chunk
grid dimension.  B and C come in groups (Mamba-2's ``n_groups``): head h
reads group h // (H / G), which the block index map picks, so no per-head
copy of B or C is made.  Validated against models.ssm.mamba2_scan in
interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(rows_ref, cols_ref, b_ref, c_ref, x_ref, h0_ref, y_ref,
                hout_ref, h_scr, *, chunk, num_chunks):
    cj = pl.program_id(2)

    @pl.when(cj == 0)
    def _init():
        h_scr[...] = h0_ref[0, 0].astype(jnp.float32)       # (P, N)

    # dt and cum = the chunk-local cumsum of dt*A arrive as rows and as
    # columns (with tail = cum[-1] - cum): Mosaic tiles 2-D (sublane,
    # lane) blocks, has no cumsum and cannot broadcast a (1, 1) value
    # along both axes, so the scan and both orientations come from the
    # wrapper
    rows = rows_ref[0, 0]                                   # (2, chunk)
    cols = cols_ref[0, 0]                                   # (chunk, 3)
    dt_r, cum_r = rows[0:1, :], rows[1:2, :]                # (1, chunk)
    dt_c, cum_c, tail_c = cols[:, 0:1], cols[:, 1:2], cols[:, 2:3]
    Bc = b_ref[0, 0].astype(jnp.float32)                    # (chunk, N)
    Cc = c_ref[0, 0].astype(jnp.float32)                    # (chunk, N)
    xh = x_ref[0, 0].astype(jnp.float32)                    # (chunk, P)

    alpha = jnp.exp(cum_c)                                  # (chunk, 1)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # decay ratio exp(cum_t - cum_s) <= 1 on the causal triangle; the
    # exponent is masked BEFORE exp so s > t cannot overflow to inf * 0
    ratio = jnp.exp(jnp.where(s_idx <= t_idx, cum_c - cum_r, -1e30))
    CB = jax.lax.dot_general(Cc, Bc, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    M = CB * ratio * dt_r                                   # (chunk, chunk)
    h = h_scr[...]
    y = jax.lax.dot_general(M, xh, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y + alpha * jax.lax.dot_general(
        Cc, h, (((1,), (1,)), ((), ())),                    # (chunk, P)
        preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    w = jnp.exp(tail_c) * dt_c                              # (chunk, 1)
    # exp(cum[-1]) as a (1, N) row: every entry of cum + tail is cum[-1]
    alpha_last = jnp.max(jnp.broadcast_to(jnp.exp(cum_c + tail_c),
                                          (chunk, h.shape[1])),
                         axis=0, keepdims=True)
    h_scr[...] = alpha_last * h + jax.lax.dot_general(
        xh, Bc * w, (((0,), (0,)), ((), ())),               # (P, N)
        preferred_element_type=jnp.float32)

    @pl.when(cj == num_chunks - 1)
    def _finish():
        hout_ref[0, 0] = h_scr[...]


def ssd_scan(dt, Bc, Cc, x, A, h0=None, *, chunk=128, interpret=None):
    """Mamba-2 SSD.  dt: (B,S,H)  Bc/Cc: (B,S,G,N)  x: (B,S,H,P)  A: (H,).

    Returns (y (B,S,H,P) fp32-accurate, h_final (B,H,P,N) fp32).
    """
    B, S, H = dt.shape
    P, G, N = x.shape[-1], Bc.shape[-2], Bc.shape[-1]
    hpg = H // G                          # heads per group
    if interpret is None:
        # nk: allow[NK03]: per-backend constant is deliberate (interpret on CPU)
        interpret = jax.default_backend() == "cpu"
    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)
    # the TPU lowering needs a chunk that is a multiple of 128 or the
    # whole (padded) sequence: min(128, S) is one or the other
    chunk = min(chunk, S)
    nc = -(-S // chunk)
    pad = nc * chunk - S

    def padseq(arr):
        return jnp.pad(arr, ((0, 0), (0, pad)) + ((0, 0),) * (arr.ndim - 2))

    # padded steps carry dt = 0: decay 1, no update, so h_final is exact
    dtp = padseq(dt).astype(jnp.float32)                    # (B, Sp, H)
    cum = jnp.cumsum((dtp * A.astype(jnp.float32)).reshape(B, nc, chunk, H),
                     axis=2).reshape(B, nc * chunk, H)
    tail = (cum.reshape(B, nc, chunk, H)[:, :, -1:]
            - cum.reshape(B, nc, chunk, H)).reshape(B, nc * chunk, H)
    rows = jnp.stack([dtp, cum], axis=1).transpose(0, 3, 1, 2)   # (B,H,2,Sp)
    cols = jnp.stack([dtp, cum, tail], axis=-1).transpose(0, 2, 1, 3)
    xp = padseq(x).transpose(0, 2, 1, 3)                    # (B, H, Sp, P)
    # (B, G, Sp, N): a block's last two dims are (chunk, N), which the
    # TPU's tiling takes; a (1, N) group slice of (B, S, G, N) it does not
    Bp = padseq(Bc).transpose(0, 2, 1, 3)
    Cp = padseq(Cc).transpose(0, 2, 1, 3)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, num_chunks=nc)
    y, hout = pl.pallas_call(
        kernel,
        grid=(B, H, nc),                  # chunk dim innermost = sequential
        in_specs=[
            pl.BlockSpec((1, 1, 2, chunk), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, 1, chunk, 3), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h // hpg, c, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h // hpg, c, 0)),
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc * chunk, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(rows, cols, Bp, Cp, xp, h0)
    return y.transpose(0, 2, 1, 3)[:, :S], hout
