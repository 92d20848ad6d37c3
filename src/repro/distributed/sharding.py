"""Sharding policy: logical param/activation axes -> mesh axes.

Logical axes:
  fsdp   weight sharding axis — ("pod","data") in multi-pod, ("data",) in
         single-pod — used for training (ZeRO-3 style) and for serving
         weights that exceed 16-way tensor parallel (mixtral);
  tp     tensor-parallel axis = "model": heads / d_ff / experts / vocab.

Activations:
  train/prefill  batch -> (pod, data)
  decode         batch -> (pod, data) when batch >= its size, else the cache
                 SEQUENCE dim -> data (distributed decode-attention: GSPMD
                 turns the softmax/PV reductions over the sharded cache into
                 small all-reduces — this is what makes long_500k fit).

Rules are path-based over the params pytree, so they apply uniformly to all
10 architectures.
"""
from __future__ import annotations

import re
import warnings
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, InputShape


class ShardingDegraded(UserWarning):
    """A leaf's intended sharding was degraded to replication because a
    tensor dim does not divide its mesh axis (jit ARGUMENT shardings must
    divide exactly).  The maths stays correct — the cost is per-device
    memory and missing parallelism on those leaves.  Warned once per
    ``param_shardings``/``decode_state_shardings`` call with every
    degraded leaf listed, so an unshardable config is visible instead of
    silently replicating."""


def _warn_degraded(fn_name: str, mesh: Mesh, degraded) -> None:
    if not degraded:
        return
    detail = ", ".join(f"{name}[dim {dim}]={size} !% {ax}={n}"
                       for name, dim, size, ax, n in degraded[:8])
    more = f" (+{len(degraded) - 8} more)" if len(degraded) > 8 else ""
    warnings.warn(
        f"{fn_name}: {len(degraded)} leaf dim(s) do not divide the "
        f"{dict(zip(mesh.axis_names, mesh.devices.shape))} mesh and were "
        f"replicated: {detail}{more}", ShardingDegraded, stacklevel=3)


def mesh_axes(mesh: Mesh):
    names = mesh.axis_names
    dp = tuple(n for n in names if n in ("pod", "data"))
    tp = "model" if "model" in names else None
    return dp, tp


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


# ---------------------------------------------------------------------------
# parameter shardings
# ---------------------------------------------------------------------------

def _param_spec(name: str, ndim: int, *, fsdp, tp, shard_fsdp: bool,
                shape=None, ax_size=None) -> P:
    """PartitionSpec for one leaf.  `ndim` includes the stacked L dim if any.

    Rules are written for the UNstacked shape; a leading layer-stack dim is
    detected by ndim and padded with None.
    """
    f = fsdp if shard_fsdp else None
    leaf = name.split("/")[-1]
    # (out of laziness, biases/norm vectors replicate except where noted)
    table = {
        "embed":    P(tp, f),
        "lm_head":  P(f, tp),
        "vision_proj": P(f, tp),
        "wq": P(f, tp), "wk": P(f, tp), "wv": P(f, tp), "wo": P(tp, f),
        "bq": P(tp), "bk": P(tp), "bv": P(tp),
        "w_gate": P(f, tp), "w_up": P(f, tp), "w_down": P(tp, f),
        "w_gate_up": P(f, tp),
        "shared_w_gate": P(f, tp), "shared_w_up": P(f, tp),
        "shared_w_down": P(tp, f),
        "router": P(f, None),
        "in_proj": P(f, tp),
        "conv_w": P(None, tp), "conv_b": P(tp),
        "x_proj": P(tp, None),
        "dt_proj": P(None, tp),
        "dt_bias": P(tp),
        "A_log": P(tp),        # mamba1: (Di,N) -> tp on Di; mamba2: (H,) -> tp
        "D": P(tp),
        "out_proj": P(tp, f),
        "norm": P(tp),
        "scale": P(), "bias": P(),
    }
    if leaf not in table:
        return P()
    spec = table[leaf]
    # MoE expert stacks have an extra leading expert dim.  Expert-parallel
    # (experts -> tp) when the count divides the axis; otherwise fall back to
    # tensor-parallel inside each expert (d_ff -> tp, d_model -> fsdp) —
    # jit argument shardings must divide exactly (e.g. qwen2-moe's 60
    # experts on a 16-way axis cannot be expert-parallel).
    if re.search(r"moe/", name) and leaf in ("w_gate", "w_up", "w_down"):
        n_exp = shape[-3] if shape is not None and len(shape) >= 3 else 0
        expert_par = ax_size is not None and n_exp % ax_size(tp) == 0
        if expert_par:
            spec = P(tp, f, None) if leaf != "w_down" else P(tp, None, f)
        else:
            spec = P(None, f, tp) if leaf != "w_down" else P(None, tp, f)
    if leaf == "A_log" and ndim - _stack_dims(name) == 2:
        spec = P(tp, None)
    # pad leading stacked-layer dims with None
    extra = ndim - len(spec)
    if extra > 0:
        spec = P(*([None] * extra + list(spec)))
    elif extra < 0:
        spec = P(*list(spec)[-ndim:]) if ndim else P()
    return spec


def _stack_dims(name: str) -> int:
    return 1 if name.startswith("layers/") or name.startswith("encoder/layers/") else 0


def param_shardings(cfg: ArchConfig, mesh: Mesh, params_shape, *,
                    shard_fsdp: bool = True):
    """Pytree of NamedSharding matching `params_shape` (an eval_shape tree)."""
    dp, tp = mesh_axes(mesh)
    fsdp = dp if len(dp) > 1 else (dp[0] if dp else None)

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def ax_size(a):
        if a is None:
            return 1
        if isinstance(a, tuple):
            return int(np.prod([sizes[x] for x in a]))
        return sizes[a]

    degraded = []

    def rule(path, leaf):
        name = _path_str(path)
        spec = _param_spec(name, leaf.ndim, fsdp=fsdp, tp=tp,
                           shard_fsdp=shard_fsdp, shape=leaf.shape,
                           ax_size=ax_size)
        # divisibility guard: jit ARGUMENT shardings must divide exactly
        # (uneven shardings are only legal for intermediates) — replicate
        # any dim that does not divide its axis, and say so.
        fixed = []
        for dim, ax in enumerate(spec):
            n = ax_size(ax)
            if n > 1 and leaf.shape[dim] % n != 0:
                degraded.append((name, dim, leaf.shape[dim], ax, n))
                fixed.append(None)
            else:
                fixed.append(ax)
        return NamedSharding(mesh, P(*fixed))

    out = jax.tree_util.tree_map_with_path(rule, params_shape)
    _warn_degraded("param_shardings", mesh, degraded)
    return out


def should_shard_fsdp_serving(cfg: ArchConfig, mesh: Mesh,
                              bytes_per_param: int = 2) -> bool:
    """Serve with weights sharded beyond TP only if TP alone won't fit."""
    _, tp = mesh_axes(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp_size = sizes.get("model", 1)
    per_dev = cfg.param_count() * bytes_per_param / tp_size
    return per_dev > 10e9          # leave room for caches on a 16 GB chip


# ---------------------------------------------------------------------------
# activation / input shardings
# ---------------------------------------------------------------------------

def batch_spec(mesh: Mesh) -> P:
    dp, _ = mesh_axes(mesh)
    return P(dp if len(dp) > 1 else (dp[0] if dp else None))


def input_shardings(cfg: ArchConfig, mesh: Mesh, inputs_shape, shape: InputShape):
    """NamedSharding tree for the input specs of this shape."""
    dp, tp = mesh_axes(mesh)
    dpa = dp if len(dp) > 1 else (dp[0] if dp else None)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_size = int(np.prod([sizes[x] for x in (dp if isinstance(dpa, tuple) else (dpa,))])) if dpa else 1
    b_ok = shape.global_batch >= dp_size

    def rule(path, leaf):
        spec = [None] * leaf.ndim
        if leaf.ndim >= 1 and b_ok:
            spec[0] = dpa
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(rule, inputs_shape)


def cache_shardings(cfg: ArchConfig, mesh: Mesh, cache_shape,
                    shape: InputShape, kv_layout: str = "heads"):
    """Decode-cache shardings.

    kv_layout='heads' (baseline): batch -> dp, kv heads -> tp (or head_dim
    -> tp for GQA with KH < tp).
    kv_layout='seq' (flash-decode, beyond-paper): batch -> dp, cache
    SEQUENCE -> tp; attention becomes a distributed partial-softmax with
    only (B, H)-sized reductions — removes the score all-reduces that
    dominate GQA decode under 'heads'.
    Mamba states: channels/heads -> tp, batch -> dp when divisible.
    """
    dp, tp = mesh_axes(mesh)
    dpa = dp if len(dp) > 1 else (dp[0] if dp else None)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_size = int(np.prod([sizes[x] for x in dp])) if dp else 1
    tp_size = sizes.get("model", 1)
    b_ok = shape.global_batch >= dp_size

    def rule(path, leaf):
        name = _path_str(path)
        nd = leaf.ndim
        if name == "pos":
            return NamedSharding(mesh, P())
        if "conv" in name:     # (L, B, K-1, C)
            spec = [None, dpa if b_ok else None, None, tp]
            return NamedSharding(mesh, P(*spec[:nd]))
        if "ssm" in name and nd == 4:   # mamba1 (L, B, Di, N)
            return NamedSharding(mesh, P(None, dpa if b_ok else None, tp, None))
        if "ssm" in name and nd == 5:   # mamba2 (L, B, H, P, N)
            return NamedSharding(mesh, P(None, dpa if b_ok else None, tp, None, None))
        if nd == 5:       # HEADS-MAJOR (L_or_apps, B, KH, S, hd) kv cache
            spec = [None] * 5
            seq_ax = None
            if b_ok:
                spec[1] = dpa
            else:
                seq_ax = "data" if "data" in mesh.axis_names else None
            if kv_layout == "seq":
                seq_ax = tp if seq_ax is None else ("data", "model")
                n = tp_size if seq_ax == tp else tp_size * dp_size
                if leaf.shape[3] % n == 0:
                    spec[3] = seq_ax
            else:
                if seq_ax is not None and leaf.shape[3] % dp_size == 0:
                    spec[3] = seq_ax      # long-context: seq -> data
                if leaf.shape[2] % tp_size == 0:
                    spec[2] = tp          # kv heads -> tp
                elif leaf.shape[4] % tp_size == 0:
                    spec[4] = tp          # head_dim -> tp (GQA, few kv heads)
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(rule, cache_shape)


def decode_state_shardings(cfg: ArchConfig, mesh: Mesh, state):
    """Shardings for a LIVE serving state dict (``DecodeSession.cache``
    subset), keyed ``k{i}``/``v{i}``/``ak{g}``/``av{g}`` (heads-major
    (B, KH, S, hd) — the per-layer, no-leading-L layout, unlike
    ``cache_shardings``' stacked init layout), ``conv{i}`` (B, K-1, C)
    and ``ssm{i}`` (mamba1 (B, Di, N) / mamba2 (B, H, P, N)).

    Tensor-parallel only: serving batch is 1, so the dp axis replicates.
    KV caches split on whole KV heads only, never on ``head_dim``: the
    decode kernel runs per shard (``kernels.ops``) and needs whole heads.
    So GQA caches whose KV heads do not divide the axis (qwen2.5-3b's 2
    on 4 chips) are replicated — each step then writes the new token's
    K/V everywhere instead of gathering the cache.  Non-divisible dims
    degrade to replication with a ``ShardingDegraded`` warning (same
    guard as ``param_shardings``)."""
    _, tp = mesh_axes(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp_size = sizes.get("model", 1)
    degraded = []

    def tp_dim(name: str, nd: int):
        if name[0] in ("k", "v", "a") and nd == 4:   # (B, KH, S, hd)
            return 1                                 # whole kv heads
        if name.startswith("conv"):                  # (B, K-1, C)
            return nd - 1
        if name.startswith("ssm"):                   # channels/heads dim
            return 1
        return None

    def rule(path, leaf):
        name = _path_str(path)
        spec = [None] * leaf.ndim
        d = tp_dim(name, leaf.ndim)
        if tp is not None and tp_size > 1 and d is not None:
            if leaf.shape[d] % tp_size == 0:
                spec[d] = tp
            else:
                degraded.append((name, d, leaf.shape[d], tp, tp_size))
        return NamedSharding(mesh, P(*spec))

    out = jax.tree_util.tree_map_with_path(rule, state)
    _warn_degraded("decode_state_shardings", mesh, degraded)
    return out
