"""jit'd public wrappers around the Pallas kernels.

GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
automatically partitioned. Please wrap the call in a shard_map"), so a
program compiled over a mesh traces the decode-path wrappers below under
``over_mesh(mesh)``: each then runs its kernel under ``jax.shard_map``,
and every shard runs it over the KV heads, SSM channels or SSM heads it
holds on the ``"model"`` axis.  Where that count does not divide the
axis, the operands are replicated and every shard runs the kernel over
all of them — the layout ``decode_state_shardings`` gives such state.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd
from repro.kernels import mamba_scan as _ms
from repro.kernels import ssd_scan as _ssd

# the mesh the enclosing program is being compiled over (None: one device)
_MESH = contextvars.ContextVar("repro_kernel_mesh", default=None)


@contextlib.contextmanager
def over_mesh(mesh):
    """Trace the kernel wrappers inside this block per shard of ``mesh``.
    A context variable, so concurrent builds on other threads are not
    affected."""
    token = _MESH.set(mesh)
    try:
        yield
    finally:
        _MESH.reset(token)


def _model_axis(n: int):
    """``"model"`` when a dim of size ``n`` splits evenly over the mesh's
    model axis, else None (replicated)."""
    mesh = _MESH.get()
    size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    return "model" if size > 1 and n % size == 0 else None


def _per_shard(fn, args, in_specs, out_specs):
    return jax.shard_map(fn, mesh=_MESH.get(), in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset"))
def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset)


@jax.jit
def _mamba1_scan(dt, Bc, Cc, x, A, h0):
    return _ms.mamba1_scan(dt, Bc, Cc, x, A, h0=h0)


def mamba1_scan(dt, Bc, Cc, x, A, h0=None):
    if h0 is None:
        h0 = jnp.zeros(x.shape[:1] + A.shape, jnp.float32)         # (B,Di,N)
    if _MESH.get() is None:
        return _mamba1_scan(dt, Bc, Cc, x, A, h0)
    ax = _model_axis(x.shape[-1])                  # Di
    seq, state = P(None, None, ax), P(None, ax, None)
    return _per_shard(_mamba1_scan, (dt, Bc, Cc, x, A, h0),
                      (seq, P(), P(), seq, P(ax, None), state),
                      (seq, state))


@jax.jit
def _flash_decode_attention(q, k_cache, v_cache, pos):
    return _fd.flash_decode_attention(q, k_cache, v_cache, pos=pos)


def flash_decode_attention(q, k_cache, v_cache, pos):
    if _MESH.get() is None:
        return _flash_decode_attention(q, k_cache, v_cache, pos)
    ax = _model_axis(k_cache.shape[1])             # KV heads
    heads, cache = P(None, None, ax, None), P(None, ax, None, None)
    return _per_shard(_flash_decode_attention, (q, k_cache, v_cache, pos),
                      (heads, cache, cache, P()), heads)


@jax.jit
def _ssd_scan(dt, Bc, Cc, x, A, h0):
    return _ssd.ssd_scan(dt, Bc, Cc, x, A, h0=h0)


def ssd_scan(dt, Bc, Cc, x, A, h0=None):
    if h0 is None:
        h0 = jnp.zeros(x.shape[:1] + x.shape[2:] + Bc.shape[-1:],
                       jnp.float32)                             # (B,H,P,N)
    if _MESH.get() is None:
        return _ssd_scan(dt, Bc, Cc, x, A, h0)
    ax = _model_axis(dt.shape[-1])                 # SSM heads
    if ax is not None:
        # a shard's heads need not start a group: give each head its own
        # group's B/C, so that the per-shard kernel maps head to group 1:1
        H, G = dt.shape[-1], Bc.shape[-2]
        Bc = jnp.repeat(Bc, H // G, axis=2)
        Cc = jnp.repeat(Cc, H // G, axis=2)
    y, state = P(None, None, ax, None), P(None, ax, None, None)
    return _per_shard(_ssd_scan, (dt, Bc, Cc, x, A, h0),
                      (P(None, None, ax), y, y, y, P(ax), state),
                      (y, state))
