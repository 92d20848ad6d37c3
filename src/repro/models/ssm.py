"""Mamba-1 (selective scan) and Mamba-2 (SSD) blocks.

Full-sequence forward uses a chunked scan: ``lax.scan`` over sequence chunks
carrying the recurrent state, with a sequential inner scan per chunk — the
working set never exceeds one chunk, which is what lets falcon-mamba's
``prefill_32k`` lower without materialising (B, S, d_inner, d_state).

The TPU-target chunked kernel lives in kernels/mamba_scan.py; ``impl='pallas'``
routes the mamba-1 inner scan through it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def causal_conv1d(x, w, b, state=None):
    """Depthwise causal conv.  x: (B,S,C), w: (K,C), state: (B,K-1,C).

    Returns (y, new_state) where new_state holds the trailing K-1 inputs.
    """
    B, S, C = x.shape
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((B, K - 1, C), x.dtype)
    xin = jnp.concatenate([state, x], axis=1)          # (B, S+K-1, C)
    y = jnp.zeros((B, S, C), jnp.float32)
    for k in range(K):
        y = y + xin[:, k:k + S].astype(jnp.float32) * w[k].astype(jnp.float32)
    new_state = xin[:, S:]
    return (y + b).astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# selective scans (chunked)
# ---------------------------------------------------------------------------

def mamba1_scan(dt, Bc, Cc, x, A, h0=None, chunk=256, impl="jnp"):
    """h_t = exp(dt_t*A)*h_{t-1} + (dt_t*x_t) outer B_t ;  y_t = h_t . C_t

    dt, x: (B,S,Di)  Bc, Cc: (B,S,N)  A: (Di,N)  h0: (B,Di,N)
    Returns y: (B,S,Di), h_final.
    """
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.mamba1_scan(dt, Bc, Cc, x, A, h0=h0)
    B, S, Di = x.shape
    N = Bc.shape[-1]
    chunk = min(chunk, S)
    nc = -(-S // chunk)
    pad = nc * chunk - S
    def padseq(a):
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    dtp, Bp, Cp, xp = map(padseq, (dt, Bc, Cc, x))
    dtp = dtp.reshape(B, nc, chunk, Di).transpose(1, 0, 2, 3)
    Bp = Bp.reshape(B, nc, chunk, N).transpose(1, 0, 2, 3)
    Cp = Cp.reshape(B, nc, chunk, N).transpose(1, 0, 2, 3)
    xp = xp.reshape(B, nc, chunk, Di).transpose(1, 0, 2, 3)
    h = h0 if h0 is not None else jnp.zeros((B, Di, N), jnp.float32)

    def chunk_step(h, blk):
        dtc, bc, cc, xc = blk      # (B, chunk, ...)

        def t_step(h, t):
            dt_t, b_t, c_t, x_t = t
            decay = jnp.exp(dt_t.astype(jnp.float32)[..., None] * A)   # (B,Di,N)
            h = decay * h + (dt_t * x_t).astype(jnp.float32)[..., None] \
                * b_t.astype(jnp.float32)[:, None, :]
            y = jnp.sum(h * c_t.astype(jnp.float32)[:, None, :], axis=-1)
            return h, y

        h, ys = jax.lax.scan(
            t_step, h,
            (dtc.transpose(1, 0, 2), bc.transpose(1, 0, 2),
             cc.transpose(1, 0, 2), xc.transpose(1, 0, 2)))
        return h, ys.transpose(1, 0, 2)   # (B, chunk, Di)

    # remat the chunk body: forward saves only the chunk-boundary states;
    # backward recomputes one chunk's inner residuals at a time (without
    # this, differentiating saves h at EVERY timestep of EVERY chunk).
    h, ys = jax.lax.scan(jax.checkpoint(chunk_step), h, (dtp, Bp, Cp, xp))
    y = ys.transpose(1, 0, 2, 3).reshape(B, nc * chunk, Di)[:, :S]
    return y, h


def mamba2_scan(dt, Bc, Cc, x, A, h0=None, chunk=64, impl="jnp"):
    # chunk=64 (vs 256 for mamba1): the mamba2 state (H, P, N) is ~16x
    # larger per step, and backward saves per-step h within a chunk.
    """SSD with scalar-per-head decay and grouped B/C.

    dt: (B,S,H)  Bc,Cc: (B,S,G,N)  x: (B,S,H,P)  A: (H,)  h: (B,H,P,N);
    head h reads group h // (H // G).  y_t = h_t . C_t  -> (B,S,H,P)
    """
    if impl == "pallas":
        from repro.kernels import ops as kops
        y, h = kops.ssd_scan(dt, Bc, Cc, x, A, h0=h0)
        return y.astype(jnp.float32), h
    B, S, H = dt.shape
    P, G, N = x.shape[-1], Bc.shape[-2], Bc.shape[-1]
    chunk = min(chunk, S)
    nc = -(-S // chunk)
    pad = nc * chunk - S
    def padseq(a):
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    dtp = padseq(dt).reshape(B, nc, chunk, H).transpose(1, 0, 2, 3)
    Bp = padseq(Bc).reshape(B, nc, chunk, G, N).transpose(1, 0, 2, 3, 4)
    Cp = padseq(Cc).reshape(B, nc, chunk, G, N).transpose(1, 0, 2, 3, 4)
    xp = padseq(x).reshape(B, nc, chunk, H, P).transpose(1, 0, 2, 3, 4)
    h = h0 if h0 is not None else jnp.zeros((B, H, P, N), jnp.float32)

    def chunk_step(h, blk):
        dtc, bc, cc, xc = blk

        def t_step(h, t):
            dt_t, b_t, c_t, x_t = t   # (B,H) (B,G,N) (B,G,N) (B,H,P)
            b_t = jnp.repeat(b_t.astype(jnp.float32), H // G, axis=1)
            c_t = jnp.repeat(c_t.astype(jnp.float32), H // G, axis=1)
            decay = jnp.exp(dt_t.astype(jnp.float32) * A)[:, :, None, None]
            upd = (dt_t[:, :, None].astype(jnp.float32) * x_t.astype(jnp.float32))[..., None] \
                * b_t[:, :, None, :]
            h = decay * h + upd
            y = jnp.einsum("bhpn,bhn->bhp", h, c_t)
            return h, y

        h, ys = jax.lax.scan(
            t_step, h,
            (dtc.transpose(1, 0, 2), bc.transpose(1, 0, 2, 3),
             cc.transpose(1, 0, 2, 3), xc.transpose(1, 0, 2, 3)))
        return h, ys.transpose(1, 0, 2, 3)

    # remat chunk body (see mamba1_scan): the mamba2 per-step state
    # (B, H, P, N) is ~16x larger, so this is what keeps zamba2 trainable.
    h, ys = jax.lax.scan(jax.checkpoint(chunk_step), h, (dtp, Bp, Cp, xp))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, nc * chunk, H, P)[:, :S]
    return y, h


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def init_mamba1(cfg, key, dtype):
    d, di = cfg.d_model, cfg.d_inner
    s = cfg.ssm
    ks = jax.random.split(key, 6)
    dt_init = jnp.log(jnp.expm1(
        jnp.exp(jax.random.uniform(ks[5], (di,), jnp.float32) *
                (np.log(0.1) - np.log(0.001)) + np.log(0.001))))
    return {
        "in_proj": jax.random.normal(ks[0], (d, 2 * di), dtype) * 0.02,
        "conv_w": jax.random.normal(ks[1], (s.d_conv, di), dtype) * 0.2,
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": jax.random.normal(ks[2], (di, s.dt_rank + 2 * s.d_state), dtype) * 0.02,
        "dt_proj": jax.random.normal(ks[3], (s.dt_rank, di), dtype) * (s.dt_rank ** -0.5),
        "dt_bias": dt_init.astype(jnp.float32),
        "A_log": jnp.log(jnp.tile(jnp.arange(1, s.d_state + 1, dtype=jnp.float32),
                                  (di, 1))),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": jax.random.normal(ks[4], (di, d), dtype) * 0.02,
    }


def mamba1_block(params, x, cache=None, *, cfg, impl="jnp"):
    """x: (B,S,D).  cache: None or {'conv': (B,K-1,Di), 'ssm': (B,Di,N)}.

    Returns (y, new_cache).
    """
    s = cfg.ssm
    di = cfg.d_inner
    xz = x @ params["in_proj"]
    xin, z = jnp.split(xz, 2, axis=-1)
    conv_state = cache["conv"] if cache is not None else None
    xc, new_conv = causal_conv1d(xin, params["conv_w"], params["conv_b"],
                                 conv_state)
    xc = jax.nn.silu(xc)
    dbc = xc @ params["x_proj"]
    dt, Bc, Cc = jnp.split(dbc, [s.dt_rank, s.dt_rank + s.d_state], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) @ params["dt_proj"].astype(jnp.float32)
                         + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    h0 = cache["ssm"] if cache is not None else None
    y, h = mamba1_scan(dt.astype(xc.dtype), Bc, Cc, xc, A, h0=h0, impl=impl)
    y = y.astype(jnp.float32) + xc.astype(jnp.float32) * params["D"]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = y @ params["out_proj"]
    new_cache = {"conv": new_conv, "ssm": h}
    return out, new_cache


def init_mamba2(cfg, key, dtype):
    d, di = cfg.d_model, cfg.d_inner
    s = cfg.ssm
    H = di // s.head_dim
    ks = jax.random.split(key, 4)
    conv_dim = di + 2 * s.n_groups * s.d_state
    return {
        "in_proj": jax.random.normal(
            ks[0], (d, di + conv_dim + H), dtype) * 0.02,
        "conv_w": jax.random.normal(ks[1], (s.d_conv, conv_dim), dtype) * 0.2,
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)),
        "D": jnp.ones((H,), jnp.float32),
        "norm": jnp.ones((di,), dtype),
        "out_proj": jax.random.normal(ks[2], (di, d), dtype) * 0.02,
    }


def mamba2_mix(params, zxbcdt, dt_mask=None, h0=None, conv_state=None, *,
               cfg, impl="jnp"):
    """The Mamba-2 mixer after ``in_proj``: causal conv over x|B|C, the
    grouped SSD scan, the D skip, the group-wise gated RMSNorm and
    ``out_proj``.  ``dt_mask`` (B, S) zeroes dt on padded steps (an
    identity update).  Returns (out, conv input xBC, h_final, conv
    state after the sequence)."""
    s = cfg.ssm
    di = cfg.d_inner
    H, P, G, N = di // s.head_dim, s.head_dim, s.n_groups, s.d_state
    z, xbc_in, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], axis=-1)
    xbc, new_conv = causal_conv1d(xbc_in, params["conv_w"], params["conv_b"],
                                  conv_state)
    xbc = jax.nn.silu(xbc)
    xin, Bc, Cc = jnp.split(xbc, [di, di + G * N], axis=-1)
    B_, S, _ = zxbcdt.shape
    xh = xin.reshape(B_, S, H, P)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    if dt_mask is not None:
        dt = dt * dt_mask[:, :, None]
    A = -jnp.exp(params["A_log"])
    y, h = mamba2_scan(dt, Bc.reshape(B_, S, G, N), Cc.reshape(B_, S, G, N),
                       xh, A, h0=h0, impl=impl)
    y = y + xh.astype(jnp.float32) * params["D"][:, None]
    # gated RMSNorm over each group's di / G channels
    y = y.reshape(B_, S, G, di // G) \
        * jax.nn.silu(z.astype(jnp.float32)).reshape(B_, S, G, di // G)
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    y = (y * jax.lax.rsqrt(var + 1e-5)).reshape(B_, S, di).astype(
        zxbcdt.dtype) * params["norm"]
    return y @ params["out_proj"], xbc_in, h, new_conv


def mamba2_block(params, x, cache=None, *, cfg, impl="jnp"):
    """Mamba-2 (SSD, grouped B/C).  cache: {'conv': (B,K-1,Di+2GN),
    'ssm': (B,H,P,N)}."""
    out, _, h, new_conv = mamba2_mix(
        params, x @ params["in_proj"],
        h0=cache["ssm"] if cache is not None else None,
        conv_state=cache["conv"] if cache is not None else None,
        cfg=cfg, impl=impl)
    return out, {"conv": new_conv, "ssm": h}
