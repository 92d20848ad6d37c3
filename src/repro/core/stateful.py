"""Stateful dynamic switching: live KV/SSM state hand-off at repartition.

The paper's video pipeline is stateless per frame, so Dynamic Switching
only has to move *requests* to the new pipeline.  A decode pipeline is
stateful: every layer carries per-stream decode state (a KV cache for
attention layers, conv+SSM state for Mamba layers), and when the split
moves from ``a`` to ``b`` the state of layers ``[min(a,b), max(a,b))``
changes sides.  ``core/state_handoff.plan_handoff`` prices the two ways
of moving it; this module *executes* the plan:

* ``transfer``  — the moved layers' state is really serialized
  (``bytes``), the link time for those bytes is priced with the current
  ``NetworkModel`` and charged to the request stream, and the payload is
  deserialized back on the target;
* ``recompute`` — the moved layers are re-prefilled on the target from
  the per-layer boundary activations the session checkpoints as it
  decodes, and the *measured* wall of that re-prefill blocks the stream.

Pieces (all operating on the same split convention: split ``s`` = layers
``[0, s)`` on the edge; the embedding rides with the edge stage, the LM
head with the cloud stage):

``StatefulStageRunner``
    Compiles decode-step and full-sequence executables for contiguous
    *unit* ranges (a unit is a decoder layer, or — for the hybrid
    family — one application of the shared attention block).  AOT
    executables are cached per ``(range, avals)`` exactly like
    ``StageRunner``'s, with ``fresh=True`` keeping "new container"
    retrace semantics.

``DecodeSession``
    The per-stream decode state: token history, one state entry per
    unit (``k{i}``/``v{i}`` heads-major KV, ``conv{i}``/``ssm{i}``
    recurrent state, ``ak{g}``/``av{g}`` shared-attn KV), the per-unit
    boundary activations that make targeted recompute possible, and a
    monotonically increasing **state epoch** — the version number the
    pool uses to decide whether a standby's view of the context can be
    trusted.  ``export_layers``/``import_layers``/``recompute_layers``
    are the hand-off primitives.

``StatefulEdgeCloudPipeline``
    ``EdgeCloudPipeline``-compatible: ``process`` runs ONE decode step
    through the compiled edge/cloud stages (measured walls, priced
    one-token boundary transfer) and advances the shared session.

``StatefulPipelinePool``
    ``PipelinePool`` whose ``activate`` executes the hand-off between
    the old and new split *before* the pointer swap: the plan's best arm
    is chosen live from the pool's current ``NetworkModel`` (predicted
    ``t_recompute`` uses a throughput spec calibrated from the session's
    own measured prefill), and the resulting ``HandoffReport`` is left
    for the caller (``PipelineManager.repartition`` /
    ``ServingEngine.execute_switch``) to stamp onto the ``SwitchReport``
    via ``strategies.apply_handoff``.  Every entry is epoch-stamped at
    build and re-synced — never trusted — when its epoch is stale at
    swap.  All four registered strategies work unchanged.

Slot pools.  The single-stream ``DecodeSession`` is one point on a
spectrum: ``repro.serving.sessions.SessionManager`` speaks the same
interface (``step_pos``/``subset``/``commit_step``/``export_layers``/
``import_layers``/``recompute_layers``/``handoff_net``) over a
slot-indexed state pool with a ``(num_slots,)`` decode position, so the
pipeline/pool/strategy machinery here serves a ragged multi-session
batch unchanged.  To that end every decode/recompute function below
accepts either a SCALAR position/length (shared by the whole batch —
the historic single-session program, kept trace-for-trace identical) or
a per-row ``(B,)`` VECTOR (each slot masks its own valid prefix; dead
slots ride along at pos 0 and never influence live rows, because every
decode op is row-independent — which is also why the row-coupled MoE
family is excluded from slot pools).
"""
from __future__ import annotations

import dataclasses
import warnings
import zlib
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.concurrency import (RANK_SESSION, RANK_STATEFUL_RUNNER,
                                    guarded_by, make_lock)
from repro.core.hardware import CLOUD_SPEC, EDGE_SPEC
from repro.core.network import NetworkModel
from repro.core import timing
from repro.core.pipeline import (BuildReport, RequestTiming, copy_to_device,
                                 tree_bytes)
from repro.core.pool import PipelinePool
from repro.core.stages import abstractify, aval_fingerprint
from repro.core.state_handoff import HandoffPlan, plan_handoff
from repro.kernels import ops as kops
from repro.models import layers as Lyr
from repro.models import ssm as SSM
from repro.models import transformer as T

_ATTN_FAMILIES = ("dense", "moe", "vlm")
_SUPPORTED = _ATTN_FAMILIES + ("ssm", "hybrid")
_DECODE_IMPLS = ("auto", "kernel", "reference")


# ---------------------------------------------------------------------------
# hand-off integrity envelope
# ---------------------------------------------------------------------------

# Envelope entry every export_layers payload carries: (epoch, pos, crc).
# A string key among the tuple tensor keys — safe because `key[0]` of
# "__meta__" is "_", never mistaken for a KV ("k"/"v"/"a") entry.
HANDOFF_META_KEY = "__meta__"


class HandoffCorrupted(RuntimeError):
    """An imported hand-off payload failed checksum/epoch validation."""


class HandoffIntegrityWarning(UserWarning):
    """A corrupt hand-off payload was detected and recovered from by
    falling back to masked recompute — the stream served no bad state."""


def payload_checksum(payload: Dict[Any, tuple]) -> int:
    """CRC32 chained over every tensor entry (meta excluded), in sorted
    key order so the digest is independent of dict insertion order."""
    crc = 0
    for k in sorted((k for k in payload if k != HANDOFF_META_KEY), key=repr):
        dtype, shape, buf = payload[k]
        crc = zlib.crc32(repr((k, dtype, tuple(shape))).encode(), crc)
        crc = zlib.crc32(buf, crc)
    return crc


# ---------------------------------------------------------------------------
# unit layout
# ---------------------------------------------------------------------------

def unit_list(cfg: ArchConfig) -> List[Tuple[str, int]]:
    """Execution-ordered state units: ``("layer", i)`` per decoder layer,
    plus ``("app", g)`` after every ``hybrid_period``-th hybrid layer."""
    if cfg.family not in _SUPPORTED:
        raise ValueError(f"stateful serving unsupported for {cfg.family!r}")
    units: List[Tuple[str, int]] = []
    for i in range(cfg.num_layers):
        units.append(("layer", i))
        if cfg.family == "hybrid" and cfg.hybrid_period \
                and (i + 1) % cfg.hybrid_period == 0:
            units.append(("app", (i + 1) // cfg.hybrid_period - 1))
    return units


def unit_index_of_split(cfg: ArchConfig, split: int) -> int:
    """Units on the edge for a split of ``split`` LAYERS: layers
    ``[0, split)`` plus any shared-attn application firing inside them."""
    split = min(max(split, 0), cfg.num_layers)
    idx = split
    if cfg.family == "hybrid" and cfg.hybrid_period:
        idx += split // cfg.hybrid_period
    return idx


def _unit_state_keys(cfg: ArchConfig, unit: Tuple[str, int]) -> Tuple[str, ...]:
    kind, idx = unit
    if kind == "app":
        return (f"ak{idx}", f"av{idx}")
    if cfg.family in _ATTN_FAMILIES:
        return (f"k{idx}", f"v{idx}")
    return (f"conv{idx}", f"ssm{idx}")


def _fit_kv(a, cap: int):
    """(B, S, KH, hd) seq-major prefill K/V -> heads-major (B, KH, cap, hd)."""
    S = a.shape[1]
    if S > cap:
        a = a[:, S - cap:]
    elif S < cap:
        a = jnp.pad(a, ((0, 0), (0, cap - S), (0, 0), (0, 0)))
    return a.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# stage runner: compiled unit-range executables
# ---------------------------------------------------------------------------

@guarded_by("_lock", "_aot_cache", "_full_cache", rank=RANK_STATEFUL_RUNNER)
class StatefulStageRunner:
    """Compiles decode/full-sequence functions over contiguous unit ranges.

    Mirrors ``StageRunner``'s caching contract: warm builds share one
    AOT-executable cache per ``(mode, range, avals)``; ``fresh=True``
    retraces+recompiles and leaves no trace ("new container").

    ``decode_impl`` selects the decode hot path: ``"kernel"`` routes
    decode attention through the Pallas ``flash_decode`` kernel and SSM
    steps through the ``mamba_scan``/``ssd_scan`` kernels; ``"reference"``
    keeps the XLA reference ops; ``"auto"`` resolves ONCE at construction
    to kernel on TPU and reference on CPU (where the Pallas kernels only
    run in interpret mode — correct, so tests pin ``"kernel"`` for
    parity, but orders slower than XLA).  ``rolled`` collapses each
    full-sequence range (prefill, recompute) and each decode span of
    mamba layers into a ``lax.scan`` over the stacked per-layer weights
    instead of an unrolled Python loop, shrinking the HLO and the
    per-range AOT compile wall; attention-layer decode ranges read each
    layer through a static index either way.  ``rolled=False`` keeps
    the unrolled trace for parity tests and the decode
    microbenchmark's A/B."""

    def __init__(self, cfg: ArchConfig, params, *, max_seq: int = 128,
                 attn_impl: str = "chunked", decode_impl: str = "auto",
                 rolled: bool = True):
        if cfg.family not in _SUPPORTED:
            raise ValueError(f"stateful serving unsupported for {cfg.family!r}")
        if decode_impl not in _DECODE_IMPLS:
            raise ValueError(f"decode_impl must be one of {_DECODE_IMPLS}, "
                             f"got {decode_impl!r}")
        self.cfg = cfg
        self.params = params
        self.max_seq = int(max_seq)
        self.attn_impl = attn_impl
        self.decode_impl = decode_impl
        if decode_impl == "auto":
            # resolved here, never inside a traced body (NK03): the
            # backend cannot change under a live runner
            decode_impl = ("kernel" if jax.default_backend() == "tpu"
                           else "reference")
        self.resolved_decode_impl = decode_impl
        self.rolled = bool(rolled)
        self.units = unit_list(cfg)
        self._aot_cache: Dict[Tuple, Any] = {}
        self._full_cache: Dict[Tuple[int, int], Any] = {}
        self._lock = make_lock("stateful-runner", RANK_STATEFUL_RUNNER)

    @property
    def _ssm_impl(self) -> str:
        return "pallas" if self.resolved_decode_impl == "kernel" else "jnp"

    def _attend(self, q, kc, vc, pos):
        """One-token attention vs the heads-major cache, routed per
        ``decode_impl``.  Both paths take/return (B, 1, H, hd) and accept
        a scalar or per-row ``(B,)`` decode position."""
        if self.resolved_decode_impl == "kernel":
            return kops.flash_decode_attention(q, kc, vc, pos + 1)
        return Lyr.decode_attention(q, kc, vc, pos=pos + 1)

    def _decode_rope(self, pos):
        """One-token rope tables with an explicit batch axis: (1, 1, hd/2)
        for a shared scalar position, (B, 1, hd/2) per-row — either way
        ``apply_rope`` sees its batched (B, S, D/2) form."""
        cfg = self.cfg
        if jnp.ndim(pos) == 0:
            cos, sin = Lyr.rope_cos_sin(pos[None], cfg.head_dim,
                                        cfg.rope_theta)
            return cos[None], sin[None]
        cos, sin = Lyr.rope_cos_sin(pos[:, None], cfg.head_dim,
                                    cfg.rope_theta)
        return cos, sin

    @staticmethod
    def _cache_write(cache, val, pos):
        """Write a one-token heads-major (B, KH, 1, hd) update at the
        decode position: one ``dynamic_update_slice`` for a shared scalar
        pos (the historic program), a vmapped per-row write for ``(B,)``."""
        if jnp.ndim(pos) == 0:
            return jax.lax.dynamic_update_slice(cache, val, (0, 0, pos, 0))
        return jax.vmap(
            lambda c, v, p: jax.lax.dynamic_update_slice(c, v, (0, p, 0))
        )(cache, val, pos)

    @property
    def num_units(self) -> int:
        """Split domain for the pool/partitioner: one unit per LAYER."""
        return self.cfg.num_layers

    def edge_param_bytes(self, split: int) -> int:
        """Layer-proportional edge parameter bytes at ``split`` (same
        contract as ``StageRunner.edge_param_bytes``; the degraded-mode
        split picker calls this)."""
        total = sum(int(a.size) * a.dtype.itemsize
                    for a in jax.tree.leaves(self.params))
        frac = (split + 1) / (self.cfg.num_layers + 2)
        return int(total * frac)

    # -- one decoder unit, one token ------------------------------------
    def _decode_unit(self, params, unit, x, cache, new, pos):
        cfg = self.cfg
        kind, idx = unit
        if kind == "app" or cfg.family in _ATTN_FAMILIES:
            kk, vk = _unit_state_keys(cfg, unit)
            p = params["shared"] if kind == "app" \
                else jax.tree.map(lambda a: a[idx], params["layers"])
            B = x.shape[0]
            h = T._apply_norm(cfg, p["ln1"], x)
            q, k, v = T._project_qkv(cfg, p["attn"], h)
            cos, sin = self._decode_rope(pos)
            q = Lyr.apply_rope(q, cos, sin)
            k = Lyr.apply_rope(k, cos, sin)
            kc = self._cache_write(
                cache[kk], k.transpose(0, 2, 1, 3).astype(cache[kk].dtype),
                pos)
            vc = self._cache_write(
                cache[vk], v.transpose(0, 2, 1, 3).astype(cache[vk].dtype),
                pos)
            new[kk], new[vk] = kc, vc
            att = self._attend(q, kc, vc, pos)
            x = x + att.reshape(B, 1, -1) @ p["attn"]["wo"]
            h2 = T._apply_norm(cfg, p["ln2"], x)
            if "moe" in p:
                ff, _ = Lyr.moe_layer(p["moe"], h2, top_k=cfg.moe.top_k,
                                      capacity_factor=cfg.moe.capacity_factor)
            else:
                ff = Lyr.mlp(p["mlp"], h2, gated=cfg.gated_mlp)
            return x + ff
        ck, sk = _unit_state_keys(cfg, unit)
        lp = jax.tree.map(lambda a: a[idx], params["layers"])
        h = T._apply_norm(cfg, lp["ln"], x)
        block = SSM.mamba1_block if cfg.family == "ssm" else SSM.mamba2_block
        y, nc = block(lp["mamba"], h,
                      cache={"conv": cache[ck], "ssm": cache[sk]}, cfg=cfg,
                      impl=self._ssm_impl)
        new[ck], new[sk] = nc["conv"], nc["ssm"]
        return x + y

    # -- one decoder unit, full sequence --------------------------------
    def _full_unit(self, params, unit, x, caches, rope_cs):
        cfg = self.cfg
        kind, idx = unit
        if kind == "app" or cfg.family in _ATTN_FAMILIES:
            kk, vk = _unit_state_keys(cfg, unit)
            p = params["shared"] if kind == "app" \
                else jax.tree.map(lambda a: a[idx], params["layers"])
            x, (k, v), _ = T.attn_block_full(cfg, p, x, rope_cs,
                                             impl=self.attn_impl,
                                             window=cfg.sliding_window)
            caches[kk] = _fit_kv(k, self.max_seq)
            caches[vk] = _fit_kv(v, self.max_seq)
            return x
        ck, sk = _unit_state_keys(cfg, unit)
        lp = jax.tree.map(lambda a: a[idx], params["layers"])
        h = T._apply_norm(cfg, lp["ln"], x)
        block = SSM.mamba1_block if cfg.family == "ssm" else SSM.mamba2_block
        y, nc = block(lp["mamba"], h, cfg=cfg)
        caches[ck], caches[sk] = nc["conv"], nc["ssm"]
        return x + y

    # -- range functions -------------------------------------------------
    # Two trace shapes per range: "unrolled" replays the Python loop over
    # units (one HLO copy per layer — O(layers) program size, and the
    # per-range AOT compile wall that dominates cold builds), "rolled"
    # scans ONE layer body over the stacked per-layer weights and caches
    # (params["layers"] is already stacked on a leading L axis).  Hybrid
    # ranges roll per homogeneous segment: runs of mamba layers scan,
    # each shared-attn application stays a single unrolled unit.
    # Attention-layer DECODE ranges never scan: at the TPU's default
    # precision XLA hoists the scanned body's f32->bf16 operand convert
    # out of the loop and materialises a bf16 copy of the whole range's
    # weights on every token (2.97 GB of temporaries for 18 qwen2.5-3b
    # layers), while a static per-layer index lets each dot read its
    # f32 weights straight from the stacked parameter.  Both traces
    # honour the same (x, new_state, bounds) contract, so the
    # session/hand-off machinery never sees the difference.

    def _segments(self, u0: int, u1: int) -> List[Tuple[str, int, int]]:
        """Units [u0, u1) as homogeneous spans: ``("layer", lo, hi)`` for
        runs of consecutive decoder layers, ``("app", g, g+1)`` for each
        shared-attention application."""
        segs: List[Tuple[str, int, int]] = []
        for kind, idx in self.units[u0:u1]:
            if kind == "layer" and segs and segs[-1][0] == "layer" \
                    and segs[-1][2] == idx:
                segs[-1] = ("layer", segs[-1][1], idx + 1)
            else:
                segs.append((kind, idx, idx + 1))
        return segs

    def _decode_ssm_span(self, params, x, cache, new, pos, lo, hi):
        """Scan the one-token mamba-layer body over layers [lo, hi)."""
        cfg = self.cfg
        lp = jax.tree.map(lambda a: a[lo:hi], params["layers"])
        conv_all = jnp.stack([cache[f"conv{i}"] for i in range(lo, hi)])
        ssm_all = jnp.stack([cache[f"ssm{i}"] for i in range(lo, hi)])
        block = SSM.mamba1_block if cfg.family == "ssm" else SSM.mamba2_block
        impl = self._ssm_impl

        def body(x, xs):
            p, c, s0 = xs
            bound = x
            h = T._apply_norm(cfg, p["ln"], x)
            y, nc = block(p["mamba"], h, cache={"conv": c, "ssm": s0},
                          cfg=cfg, impl=impl)
            return x + y, (bound, nc["conv"], nc["ssm"])

        x, (bounds, convs, ssms) = jax.lax.scan(body, x,
                                                (lp, conv_all, ssm_all))
        for j, i in enumerate(range(lo, hi)):
            new[f"conv{i}"], new[f"ssm{i}"] = convs[j], ssms[j]
        return x, bounds

    def _make_decode_fn_rolled(self, u0: int, u1: int):
        segs = self._segments(u0, u1)
        cfg = self.cfg

        def fn(params, x, cache, pos):
            new: Dict[str, Any] = {}
            parts = []
            for kind, lo, hi in segs:
                if kind == "app" or cfg.family in _ATTN_FAMILIES:
                    for i in range(lo, hi):
                        parts.append(x[None])
                        x = self._decode_unit(params, (kind, i), x, cache,
                                              new, pos)
                else:
                    x, b = self._decode_ssm_span(params, x, cache, new,
                                                 pos, lo, hi)
                    parts.append(b)
            b = jnp.concatenate(parts, 0) if parts \
                else jnp.zeros((0,) + x.shape, x.dtype)
            return x, new, b
        return fn

    def _make_decode_fn_unrolled(self, u0: int, u1: int):
        units = self.units[u0:u1]

        def fn(params, x, cache, pos):
            new: Dict[str, Any] = {}
            bounds = []
            for unit in units:
                bounds.append(x)
                x = self._decode_unit(params, unit, x, cache, new, pos)
            b = jnp.stack(bounds) if bounds \
                else jnp.zeros((0,) + x.shape, x.dtype)
            return x, new, b
        return fn

    def _make_decode_fn(self, u0: int, u1: int):
        if self.rolled:
            return self._make_decode_fn_rolled(u0, u1)
        return self._make_decode_fn_unrolled(u0, u1)

    def _full_attn_span(self, params, x, caches, rope_cs, lo, hi):
        cfg = self.cfg
        lp = jax.tree.map(lambda a: a[lo:hi], params["layers"])

        def body(x, p):
            bound = x
            x, (k, v), _ = T.attn_block_full(cfg, p, x, rope_cs,
                                             impl=self.attn_impl,
                                             window=cfg.sliding_window)
            return x, (bound, k, v)

        x, (bounds, ks, vs) = jax.lax.scan(body, x, lp)
        for j, i in enumerate(range(lo, hi)):
            caches[f"k{i}"] = _fit_kv(ks[j], self.max_seq)
            caches[f"v{i}"] = _fit_kv(vs[j], self.max_seq)
        return x, bounds

    def _full_ssm_span(self, params, x, caches, lo, hi):
        cfg = self.cfg
        lp = jax.tree.map(lambda a: a[lo:hi], params["layers"])
        block = SSM.mamba1_block if cfg.family == "ssm" else SSM.mamba2_block

        def body(x, p):
            bound = x
            h = T._apply_norm(cfg, p["ln"], x)
            y, nc = block(p["mamba"], h, cfg=cfg)
            return x + y, (bound, nc["conv"], nc["ssm"])

        x, (bounds, convs, ssms) = jax.lax.scan(body, x, lp)
        for j, i in enumerate(range(lo, hi)):
            caches[f"conv{i}"], caches[f"ssm{i}"] = convs[j], ssms[j]
        return x, bounds

    def _make_full_fn_rolled(self, u0: int, u1: int):
        segs = self._segments(u0, u1)
        cfg = self.cfg

        def fn(params, x):
            S = x.shape[1]
            rope_cs = Lyr.rope_cos_sin(jnp.arange(S), cfg.head_dim,
                                       cfg.rope_theta)
            caches: Dict[str, Any] = {}
            parts = []
            for kind, lo, hi in segs:
                if kind == "app":
                    for g in range(lo, hi):
                        parts.append(x[None])
                        x = self._full_unit(params, ("app", g), x, caches,
                                            rope_cs)
                elif cfg.family in _ATTN_FAMILIES:
                    x, b = self._full_attn_span(params, x, caches, rope_cs,
                                                lo, hi)
                    parts.append(b)
                else:
                    x, b = self._full_ssm_span(params, x, caches, lo, hi)
                    parts.append(b)
            b = jnp.concatenate(parts, 0) if parts \
                else jnp.zeros((0,) + x.shape, x.dtype)
            return x, caches, b
        return fn

    def _make_full_fn_unrolled(self, u0: int, u1: int):
        units = self.units[u0:u1]

        def fn(params, x):
            S = x.shape[1]
            rope_cs = Lyr.rope_cos_sin(jnp.arange(S), self.cfg.head_dim,
                                       self.cfg.rope_theta)
            caches: Dict[str, Any] = {}
            bounds = []
            for unit in units:
                bounds.append(x)
                x = self._full_unit(params, unit, x, caches, rope_cs)
            b = jnp.stack(bounds) if bounds \
                else jnp.zeros((0,) + x.shape, x.dtype)
            return x, caches, b
        return fn

    def _make_full_fn(self, u0: int, u1: int):
        if self.rolled:
            return self._make_full_fn_rolled(u0, u1)
        return self._make_full_fn_unrolled(u0, u1)

    # -- masked re-prefill (the recompute hand-off arm) ------------------
    # The recompute arm runs at whatever context length the stream has
    # reached, so an exact-shape jit would recompile on every hand-off.
    # Instead the context is zero-padded to ``max_seq`` (ONE compile per
    # unit range, ever) and correctness beyond the live length is
    # enforced the way bucketed prefills do it: causal attention already
    # ignores the pad for valid rows (pad rows are masked out of the
    # cache), and the recurrent state freezes at the live length because
    # a masked dt makes every padded step an identity update
    # (decay = exp(0 * A) = 1, update = 0).

    def _masked_mamba(self, lp, x, mask, length):
        cfg = self.cfg
        s = cfg.ssm
        di = cfg.d_inner
        B = x.shape[0]
        # mask: (CL,) shared across the batch, or (B, CL) per-row (slot
        # pools); either way dt sees its batched (B, CL, 1) form — the
        # shared path broadcasts exactly as it always did
        mask_b = mask[None] if mask.ndim == 1 else mask
        h = T._apply_norm(cfg, lp["ln"], x)
        p = lp["mamba"]
        if cfg.family == "ssm":            # mamba1
            xz = h @ p["in_proj"]
            xin, z = jnp.split(xz, 2, axis=-1)
            xc, _ = SSM.causal_conv1d(xin, p["conv_w"], p["conv_b"])
            xc = jax.nn.silu(xc)
            dbc = xc @ p["x_proj"]
            dt, Bc, Cc = jnp.split(dbc, [s.dt_rank, s.dt_rank + s.d_state],
                                   axis=-1)
            dt = jax.nn.softplus(
                dt.astype(jnp.float32) @ p["dt_proj"].astype(jnp.float32)
                + p["dt_bias"]) * mask_b[:, :, None]
            A = -jnp.exp(p["A_log"])
            y, hs = SSM.mamba1_scan(dt.astype(xc.dtype), Bc, Cc, xc, A)
            y = y.astype(jnp.float32) + xc.astype(jnp.float32) * p["D"]
            y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
            out = y @ p["out_proj"]
            conv_src = xin
        else:                              # mamba2 (hybrid backbone)
            H = di // s.head_dim
            N = s.d_state
            zxbcdt = h @ p["in_proj"]
            z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * N], axis=-1)
            xbc_c, _ = SSM.causal_conv1d(xbc, p["conv_w"], p["conv_b"])
            xbc_c = jax.nn.silu(xbc_c)
            xin, Bc, Cc = jnp.split(xbc_c, [di, di + N], axis=-1)
            S_len = x.shape[1]
            xh = xin.reshape(B, S_len, H, s.head_dim)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"]) \
                * mask_b[:, :, None]
            A = -jnp.exp(p["A_log"])
            y, hs = SSM.mamba2_scan(dt, Bc, Cc, xh, A)
            y = y + xh.astype(jnp.float32) * p["D"][:, None]
            y = y.reshape(B, S_len, di).astype(x.dtype)
            y = y * jax.nn.silu(z)
            var = jnp.mean(jnp.square(y.astype(jnp.float32)), axis=-1,
                           keepdims=True)
            y = (y * jax.lax.rsqrt(var + 1e-5).astype(y.dtype)) * p["norm"]
            out = y @ p["out_proj"]
            conv_src = xbc
        # conv state = the K-1 raw inputs trailing the LIVE length, not
        # the pad (dynamic_slice at the traced length; per-row lengths
        # slice each row at its own live prefix)
        K = p["conv_w"].shape[0]
        C = conv_src.shape[-1]
        cat = jnp.concatenate(
            [jnp.zeros((B, K - 1, C), conv_src.dtype), conv_src], axis=1)
        if jnp.ndim(length) == 0:
            conv_state = jax.lax.dynamic_slice(
                cat, (0, length, 0), (B, K - 1, C))
        else:
            conv_state = jax.vmap(
                lambda c, l: jax.lax.dynamic_slice(c, (l, 0), (K - 1, C))
            )(cat, length)
        return x + out, {"conv": conv_state, "ssm": hs}

    def _make_recompute_fn(self, u0: int, u1: int):
        units = self.units[u0:u1]
        cfg = self.cfg
        CL = self.max_seq

        def fn(params, x, length):
            # x: (B, CL, D) zero-padded context; length: live prefix — a
            # scalar shared by the batch or per-row (B,) (slot pools)
            if jnp.ndim(length) == 0:
                mask = (jnp.arange(CL) < length)
                m = mask[None, :, None, None]
            else:
                mask = (jnp.arange(CL)[None, :] < length[:, None])
                m = mask[:, :, None, None]
            rope_cs = Lyr.rope_cos_sin(jnp.arange(CL), cfg.head_dim,
                                       cfg.rope_theta)
            caches: Dict[str, Any] = {}
            for unit in units:
                kind, idx = unit
                if kind == "app" or cfg.family in _ATTN_FAMILIES:
                    kk, vk = _unit_state_keys(cfg, unit)
                    p = params["shared"] if kind == "app" \
                        else jax.tree.map(lambda a: a[idx], params["layers"])
                    x, (k, v), _ = T.attn_block_full(
                        cfg, p, x, rope_cs, impl=self.attn_impl,
                        window=cfg.sliding_window)
                    caches[kk] = (k * m).transpose(0, 2, 1, 3)
                    caches[vk] = (v * m).transpose(0, 2, 1, 3)
                else:
                    ck, sk = _unit_state_keys(cfg, unit)
                    lp = jax.tree.map(lambda a: a[idx], params["layers"])
                    x, st = self._masked_mamba(lp, x, mask, length)
                    caches[ck], caches[sk] = st["conv"], st["ssm"]
            return caches
        return fn

    def recompute_fn(self, u0: int, u1: int):
        """Cached masked re-prefill fn for units [u0, u1) — compiled once
        per range, reused at every context length."""
        with self._lock:
            key = ("recompute", u0, u1)
            if key not in self._full_cache:
                self._full_cache[key] = jax.jit(
                    self._make_recompute_fn(u0, u1))
            return self._full_cache[key]

    # -- masked admission (slot pools) -----------------------------------
    # Admitting a session into a live slot pool is a masked prefill at the
    # pool's fixed (B, max_seq) bucket: the same zero-pad + masked-dt
    # trick as the recompute arm, extended to also return the per-unit
    # boundary activations and the logits at each row's last live token.
    # Compiled once per bucket shape, reused for every mid-flight join.

    def _make_admit_fn(self):
        cfg = self.cfg
        CL = self.max_seq
        units = self.units

        def fn(params, tokens, length):
            # tokens: (B, CL) zero-padded; length: live prefix — scalar
            # shared by the batch or per-row (B,)
            B = tokens.shape[0]
            if jnp.ndim(length) == 0:
                mask2 = (jnp.arange(CL) < length)[None]
            else:
                mask2 = (jnp.arange(CL)[None, :] < length[:, None])
            m3 = mask2[:, :, None]
            m4 = mask2[:, :, None, None]
            rope_cs = Lyr.rope_cos_sin(jnp.arange(CL), cfg.head_dim,
                                       cfg.rope_theta)
            x = params["embed"][tokens]
            caches: Dict[str, Any] = {}
            bounds = []
            for unit in units:
                # boundary checkpoints are stored masked so slot buffers
                # keep the zero-beyond-live-prefix invariant the sliced
                # KV export/import path relies on
                bounds.append(x * m3)
                kind, idx = unit
                if kind == "app" or cfg.family in _ATTN_FAMILIES:
                    kk, vk = _unit_state_keys(cfg, unit)
                    p = params["shared"] if kind == "app" \
                        else jax.tree.map(lambda a: a[idx], params["layers"])
                    x, (k, v), _ = T.attn_block_full(
                        cfg, p, x, rope_cs, impl=self.attn_impl,
                        window=cfg.sliding_window)
                    caches[kk] = (k * m4).transpose(0, 2, 1, 3)
                    caches[vk] = (v * m4).transpose(0, 2, 1, 3)
                else:
                    ck, sk = _unit_state_keys(cfg, unit)
                    lp = jax.tree.map(lambda a: a[idx], params["layers"])
                    x, st = self._masked_mamba(lp, x, mask2, length)
                    caches[ck], caches[sk] = st["conv"], st["ssm"]
            D = x.shape[-1]
            if jnp.ndim(length) == 0:
                last = jax.lax.dynamic_slice(x, (0, length - 1, 0),
                                             (B, 1, D))
            else:
                # rows with length 0 (dead slots) clamp to position 0 and
                # produce garbage logits the caller masks out
                last = jax.vmap(
                    lambda xi, l: jax.lax.dynamic_slice(xi, (l - 1, 0),
                                                        (1, D))
                )(x, length)
            h = T._apply_norm(cfg, params["final_norm"], last)
            logits = (h[:, -1] @ T.lm_head_weights(cfg, params)).astype(
                jnp.float32)
            b = jnp.stack(bounds) if bounds \
                else jnp.zeros((0, B, CL, x.shape[-1]), x.dtype)
            return logits, caches, b
        return fn

    def admit_fn(self):
        """Cached masked-admission fn ``(params, tokens, length) ->
        (last_logits, caches, bounds)`` over the full unit range."""
        with self._lock:
            if ("admit",) not in self._full_cache:
                self._full_cache[("admit",)] = jax.jit(self._make_admit_fn())
            return self._full_cache[("admit",)]

    def _make_embed_fn(self):
        def fn(params, tokens):
            return params["embed"][tokens]
        return fn

    def _make_head_fn(self):
        cfg = self.cfg

        def fn(params, x):
            x = T._apply_norm(cfg, params["final_norm"], x)
            return (x[:, -1] @ T.lm_head_weights(cfg, params)).astype(
                jnp.float32)
        return fn

    # -- compiled executables -------------------------------------------
    def executable(self, mode: str, u0: int, u1: int, params, *args,
                   fresh: bool = False, shardings=None, mesh=None):
        """AOT executable for a unit range, specialized to the arg avals.

        ``mode``: ``decode`` (params, x, cache, pos), ``full`` (params, x),
        ``embed`` (params, tokens), ``head`` (params, x).

        ``mesh`` + ``shardings`` compile a tensor-parallel executable:
        ``shardings`` is the jit ``in_shardings`` tuple over
        ``(params, *args)`` (prefix pytrees allowed) and the cache keys on
        the mesh identity, so single-device and per-mesh executables for
        the same range coexist.  Its Pallas kernels are traced under
        ``kernels.ops.over_mesh``: each shard runs them over the heads or
        channels it holds (GSPMD cannot partition a Mosaic kernel)."""
        makers = {"decode": lambda: self._make_decode_fn(u0, u1),
                  "full": lambda: self._make_full_fn(u0, u1),
                  "embed": self._make_embed_fn,
                  "head": self._make_head_fn}
        avals = abstractify(args)
        mesh_key = None if mesh is None else (tuple(mesh.axis_names),
                                              tuple(mesh.devices.shape))
        key = (mode, u0, u1, mesh_key) + aval_fingerprint(avals)
        if not fresh:
            with self._lock:
                hit = self._aot_cache.get(key)
            if hit is not None:
                return hit
        if mesh is None:
            compiled = jax.jit(makers[mode]()).lower(
                abstractify(params), *avals).compile()
        else:
            with mesh, kops.over_mesh(mesh):
                compiled = jax.jit(makers[mode](),
                                   in_shardings=shardings).lower(
                    abstractify(params), *avals).compile()
        if not fresh:
            with self._lock:
                self._aot_cache[key] = compiled
        return compiled

    def full_fn(self, u0: int, u1: int):
        """Warm (retracing-jit) full-sequence fn — the prefill/recompute
        path, shape-polymorphic over the growing context."""
        with self._lock:
            if (u0, u1) not in self._full_cache:
                self._full_cache[(u0, u1)] = jax.jit(
                    self._make_full_fn(u0, u1))
            return self._full_cache[(u0, u1)]


# ---------------------------------------------------------------------------
# decode session: the stream's state
# ---------------------------------------------------------------------------

class DecodeSession:
    """Per-stream decode state shared by every pipeline in the pool.

    ``epoch`` is the state version: bumped on prefill and on every
    committed decode step.  A pool entry stamped with an older epoch was
    built against a stale view of the context and must be re-synced at
    activation, never trusted."""

    def __init__(self, runner: StatefulStageRunner):
        self.runner = runner
        self.cfg = runner.cfg
        self.cache: Dict[str, Any] = {}
        self.tokens: Optional[np.ndarray] = None   # (B, T) context so far
        self.bounds: Optional[np.ndarray] = None   # (U, B, T, D) per-unit in
        self.last_logits = None
        self.pos = 0
        self.epoch = 0
        self.calib_spec = CLOUD_SPEC       # refined by prefill()
        # serialization-path calibration (refined by prefill()): fixed
        # per-payload overhead and sustained throughput of the
        # export->import round trip, folded into hand-off pricing
        self._ser_overhead_s: Optional[float] = None
        self._ser_bps: Optional[float] = None
        self._lock = make_lock("session", RANK_SESSION)

    @property
    def batch(self) -> int:
        return 1 if self.tokens is None else self.tokens.shape[0]

    # -- lifecycle -------------------------------------------------------
    def prefill(self, tokens) -> None:
        """Run the whole stack over the prompt, building every unit's
        state + boundary checkpoints, and calibrate the recompute-arm
        throughput from the measured wall."""
        tokens = jnp.asarray(tokens)
        r = self.runner
        U = len(r.units)
        if tokens.shape[1] > r.max_seq:
            raise ValueError(f"prompt {tokens.shape[1]} > max_seq {r.max_seq}")
        x = r.params["embed"][tokens]
        x, caches, bounds = r.full_fn(0, U)(r.params, x)
        logits = (T._apply_norm(self.cfg, r.params["final_norm"], x)[:, -1]
                  @ T.lm_head_weights(self.cfg, r.params)).astype(jnp.float32)
        jax.block_until_ready(logits)
        # calibration wall from a second, warm run: the first call paid
        # jit compilation, which would make the recompute arm look orders
        # of magnitude slower than it is.  Host wall (never stream time):
        # this prices THIS HOST's recompute throughput.
        with timing.measure() as m:
            jax.block_until_ready(r.full_fn(0, U)(r.params, x)[0])
        wall = m.wall
        with self._lock:
            self.cache = dict(caches)
            self.tokens = np.asarray(tokens)
            self.bounds = np.asarray(bounds)
            self.last_logits = logits
            self.pos = int(tokens.shape[1])
            self.epoch += 1
        self._calibrate(wall)
        self._calibrate_serialization()

    def _calibrate(self, wall: float) -> None:
        """Recompute-arm pricing spec from this host's measured prefill
        throughput (flops actually achieved, mfu folded in)."""
        from repro.core.profiler import _layer_flops
        toks = self.batch * self.pos
        flops = sum(_layer_flops(self.cfg, k, tokens=toks, seq=self.pos)
                    for k in self.cfg.layer_kinds())
        if wall > 0 and flops > 0:
            self.calib_spec = dataclasses.replace(
                CLOUD_SPEC, name="host-calibrated", flops=flops / wall,
                mfu=1.0)

    def _calibrate_serialization(self) -> None:
        """Measure the export->import round trip at two payload sizes and
        split it into fixed overhead + throughput.  The hand-off's
        serialization shares the transfer path with the wire, so pricing
        that ignores it would call ``transfer`` on fat links where the
        copy itself is the bottleneck."""
        L = self.cfg.num_layers
        half = max(1, L // 2)

        def round_trip(hi):
            payload, n = self.export_layers(0, hi)
            self.import_layers(payload)
            return n
        round_trip(L)                       # warm dispatch paths

        def timed(hi):
            # host wall: calibrates THIS HOST's serialization throughput
            # for hand-off pricing, never charged to the stream
            best, n = float("inf"), 0
            for _ in range(3):              # min-of-3: robust to GC spikes
                with timing.measure() as m:
                    n = round_trip(hi)
                best = min(best, m.wall)
            return best, n
        t_full, n_full = timed(L)
        t_half, n_half = timed(half)
        if n_full > n_half and t_full > t_half:
            bps = (n_full - n_half) / (t_full - t_half)
            self._ser_bps = bps
            self._ser_overhead_s = max(0.0, t_full - n_full / bps)
        else:                               # degenerate (1-layer stacks)
            self._ser_bps = None
            self._ser_overhead_s = t_full

    def handoff_net(self, net: NetworkModel) -> NetworkModel:
        """Effective link model for hand-off pricing: the measured
        serialization overhead adds to the latency and its throughput
        composes harmonically with the wire bandwidth."""
        if self._ser_overhead_s is None:
            return net
        lat = net.latency_ms + self._ser_overhead_s * 1e3
        bw = net.bandwidth_mbps
        if self._ser_bps:
            ser_mbps = self._ser_bps * 8 / 1e6
            bw = 1.0 / (1.0 / bw + 1.0 / ser_mbps)
        return NetworkModel(bw, latency_ms=lat)

    def next_token(self):
        """Greedy next token from the last logits (the decode stream)."""
        assert self.last_logits is not None, "session not prefilled"
        return jnp.argmax(self.last_logits, -1)[:, None].astype(jnp.int32)

    def step_pos(self):
        """Decode-position operand for the next step.  The single-stream
        session shares one scalar across its batch; slot pools override
        this with a per-slot ``(num_slots,)`` vector — the pipeline
        derives its compiled position aval from this shape."""
        return jnp.int32(self.pos)

    def commit_step(self, token, new_state: Dict[str, Any], bounds,
                    logits) -> None:
        """Land one decode step: state updates, boundary checkpoints,
        context growth, epoch bump."""
        with self._lock:
            self.cache.update(new_state)
            self.tokens = np.concatenate(
                [self.tokens, timing.fetch(token)], axis=1)
            self.bounds = np.concatenate(
                [self.bounds, timing.fetch(bounds)], axis=2)
            self.last_logits = logits
            self.pos += 1
            self.epoch += 1

    def subset(self, u0: int, u1: int) -> Dict[str, Any]:
        """The state entries a stage over units [u0, u1) reads/writes."""
        with self._lock:
            out = {}
            for unit in self.runner.units[u0:u1]:
                for k in _unit_state_keys(self.cfg, unit):
                    out[k] = self.cache[k]
            return out

    # -- hand-off primitives ---------------------------------------------
    def export_layers(self, lo: int, hi: int
                      ) -> Tuple[Dict[str, tuple], int]:
        """Really serialize the state of layers [lo, hi): KV sliced to the
        live context, recurrent state whole.  Returns (payload, nbytes).

        The payload carries a ``HANDOFF_META_KEY`` integrity envelope —
        ``(epoch, pos, crc32)`` — that ``import_layers`` validates before
        committing anything, so in-transit corruption is detected rather
        than served."""
        u0 = unit_index_of_split(self.cfg, lo)
        u1 = unit_index_of_split(self.cfg, hi)
        payload: Dict[str, tuple] = {}
        nbytes = 0
        with self._lock:
            for unit in self.runner.units[u0:u1]:
                for k in _unit_state_keys(self.cfg, unit):
                    arr = timing.fetch(self.cache[k])
                    if k[0] in ("k", "v", "a"):      # KV: valid region only
                        arr = arr[:, :, :self.pos]
                    buf = arr.tobytes()
                    payload[k] = (str(arr.dtype), arr.shape, buf)
                    nbytes += len(buf)
            payload[HANDOFF_META_KEY] = (self.epoch, self.pos,
                                         payload_checksum(payload))
        return payload, nbytes

    def validate_payload(self, payload: Dict[str, tuple]) -> None:
        """Raise ``HandoffCorrupted`` unless the payload's envelope
        matches its bytes and the session's current epoch.  A payload
        without an envelope passes (pre-envelope callers)."""
        meta = payload.get(HANDOFF_META_KEY)
        if meta is None:
            return
        epoch, _pos, crc = meta
        with self._lock:
            live_epoch = self.epoch
        if epoch != live_epoch:
            raise HandoffCorrupted(f"hand-off epoch {epoch} != session "
                                   f"epoch {live_epoch}: stale payload")
        actual = payload_checksum(payload)
        if crc != actual:
            raise HandoffCorrupted(f"hand-off checksum mismatch: envelope "
                                   f"{crc:#010x} != bytes {actual:#010x}")

    def import_layers(self, payload: Dict[str, tuple]) -> None:
        """Deserialize an ``export_layers`` payload back into the state.

        KV rows at positions >= ``pos`` are zero by invariant (zero-init
        caches, masked recompute), so a sliced KV payload reassembles
        into a fresh zero buffer with ONE host->device transfer instead
        of an in-place scatter against the old cache.

        Validates the integrity envelope and fully decodes every entry
        BEFORE committing anything: on corruption this raises
        ``HandoffCorrupted`` with the session state untouched, so a
        caller's recompute fallback starts from pristine state."""
        self.validate_payload(payload)
        decoded: Dict[str, np.ndarray] = {}
        try:
            for k, (dtype, shape, buf) in payload.items():
                if k == HANDOFF_META_KEY:
                    continue
                decoded[k] = np.frombuffer(buf, dtype=dtype).reshape(shape)
        except (ValueError, TypeError) as e:   # short buffer / bad dtype
            raise HandoffCorrupted(f"undecodable hand-off entry "
                                   f"{k!r}: {e}") from None
        with self._lock:
            for k, arr in decoded.items():
                if k[0] in ("k", "v", "a"):
                    full = np.zeros(self.cache[k].shape, arr.dtype)
                    full[:, :, :arr.shape[2]] = arr
                    self.cache[k] = timing.upload(full)
                else:
                    self.cache[k] = timing.upload(arr)

    def recompute_layers(self, lo: int, hi: int) -> None:
        """Re-prefill layers [lo, hi) over the full live context from the
        boundary checkpoint entering layer ``lo`` (measured by the caller).

        Runs the masked fixed-shape path: padded to ``max_seq`` so the
        compiled executable is reused at every context length."""
        u0 = unit_index_of_split(self.cfg, lo)
        u1 = unit_index_of_split(self.cfg, hi)
        if u0 >= u1:
            return
        r = self.runner
        with self._lock:
            x0 = self.bounds[u0]                       # (B, T, D)
        B, T_len, D = x0.shape
        x_pad = np.zeros((B, r.max_seq, D), x0.dtype)
        x_pad[:, :T_len] = x0
        caches = r.recompute_fn(u0, u1)(r.params, timing.upload(x_pad),
                                        jnp.int32(T_len))
        timing.block(caches)
        with self._lock:
            self.cache.update(caches)

    def replace_state(self, entries: Dict[str, Any]) -> None:
        """Swap state buffers wholesale — the mesh-reshard path, where the
        values are numerically identical and only device placement moved."""
        with self._lock:
            self.cache.update(entries)

    # -- test/benchmark support ------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            return {"cache": dict(self.cache), "tokens": self.tokens,
                    "bounds": self.bounds, "logits": self.last_logits,
                    "pos": self.pos, "epoch": self.epoch}

    def restore(self, snap: dict) -> None:
        with self._lock:
            self.cache = dict(snap["cache"])
            self.tokens, self.bounds = snap["tokens"], snap["bounds"]
            self.last_logits = snap["logits"]
            self.pos, self.epoch = snap["pos"], snap["epoch"]


# ---------------------------------------------------------------------------
# pipeline: one split, EdgeCloudPipeline-compatible
# ---------------------------------------------------------------------------

class StatefulEdgeCloudPipeline:
    """Two compiled decode stages over a shared ``DecodeSession``.

    ``process`` runs ONE decode step: the edge stage covers the embedding
    plus layers [0, split) (measured wall, scaled by ``edge_scale``), the
    one-token hidden state crossing the link is priced with the current
    ``NetworkModel``, and the cloud stage covers layers [split, L) plus
    the LM head (measured wall).  The session — state, boundaries, token
    history — advances once per served request."""

    def __init__(self, runner: StatefulStageRunner, split: int,
                 net: NetworkModel, *, session: DecodeSession,
                 edge_scale: float = CLOUD_SPEC.flops / EDGE_SPEC.flops,
                 owns_weights: bool = False,
                 mesh_shape: Optional[tuple] = None):
        self.runner = runner
        self.session = session
        self.split = min(max(int(split), 0), runner.num_units)
        self.net = net
        self.edge_scale = edge_scale
        self.owns_weights = owns_weights
        self.mesh_shape = tuple(mesh_shape) if mesh_shape else None
        self.params = runner.params
        # cloud-stage weight view: ``params`` single-device, a sharded
        # mesh-resident copy when ``mesh_shape`` is set (mirrors
        # ``EdgeCloudPipeline``; the edge stage always stays single-device)
        self.cloud_params = runner.params
        self._cloud_psh = None              # param shardings (mesh builds)
        self._cloud_state_shardings = None  # cloud-range decode state
        self._repl = None                   # replicated sharding on the mesh
        self._edge_sharding = None          # where edge-stage operands live
        self._u_edge = unit_index_of_split(runner.cfg, self.split)
        self._u_all = len(runner.units)
        self.embed_fn = None
        self.edge_fn = None
        self.cloud_fn = None
        self.head_fn = None

    # -- build -----------------------------------------------------------
    def build(self, sample_inputs=None, *, cold: bool,
              reload_from: Optional[str] = None) -> BuildReport:
        rep = BuildReport()
        r = self.runner
        if reload_from is not None or self.owns_weights:
            with timing.span("build.weights", timed=True,
                             bytes=tree_bytes(r.params)) as m:
                if reload_from is not None:
                    from repro.checkpoint import load_pytree
                    self.params = load_pytree(reload_from, like=r.params)
                else:
                    self.params = copy_to_device(r.params)
                timing.block(self.params)
            rep.t_weights = m.wall
        else:
            self.params = r.params

        self._edge_sharding = getattr(
            jax.tree.leaves(self.params)[0], "sharding", None)

        s = self.session
        B, D = s.batch, r.cfg.d_model
        x_av = jax.ShapeDtypeStruct((B, 1, D), jnp.float32)
        tok_av = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        # scalar for the single-stream session, (num_slots,) for slot
        # pools — the compiled stages follow the session's position shape
        pos_av = jax.ShapeDtypeStruct(jnp.shape(s.step_pos()), jnp.int32)
        with timing.span("build.exec", timed=True, stage="embed") as m_embed:
            self.embed_fn = r.executable("embed", 0, 0, self.params, tok_av,
                                         fresh=cold)
        with timing.span("build.exec", timed=True, stage="edge") as m_edge:
            self.edge_fn = r.executable(
                "decode", 0, self._u_edge, self.params, x_av,
                s.subset(0, self._u_edge), pos_av, fresh=cold)
        rep.t_compile_edge = m_embed.wall + m_edge.wall
        cache_cloud = s.subset(self._u_edge, self._u_all)
        shardings = head_shardings = mesh = None
        if self.mesh_shape is None:
            self.cloud_params = self.params
            self._cloud_psh = self._cloud_state_shardings = self._repl = None
        else:
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.distributed.sharding import (decode_state_shardings,
                                                    param_shardings)
            from repro.launch.mesh import make_cloud_mesh
            mesh = make_cloud_mesh(self.mesh_shape)
            psh = param_shardings(r.cfg, mesh, abstractify(self.params),
                                  shard_fsdp=False)
            csh = decode_state_shardings(r.cfg, mesh,
                                         abstractify(cache_cloud))
            repl = NamedSharding(mesh, PartitionSpec())
            self._cloud_psh, self._cloud_state_shardings = psh, csh
            self._repl = repl
            shardings, head_shardings = (psh, repl, csh, repl), (psh, repl)
        with timing.span("build.exec", timed=True, stage="cloud") as m_cloud:
            self.cloud_fn = r.executable(
                "decode", self._u_edge, self._u_all, self.params, x_av,
                cache_cloud, pos_av, fresh=cold, shardings=shardings,
                mesh=mesh)
        with timing.span("build.exec", timed=True, stage="head") as m_head:
            self.head_fn = r.executable("head", 0, 0, self.params, x_av,
                                        fresh=cold, shardings=head_shardings,
                                        mesh=mesh)
        rep.t_compile_cloud = m_cloud.wall + m_head.wall
        if mesh is not None:
            # place the cloud weight copy on the mesh at build time, so a
            # prebuilt standby's on-stream reshard is ~0
            with timing.span("build.reshard", timed=True) as m:
                self.cloud_params = jax.device_put(self.params,
                                                   self._cloud_psh)
                timing.block(self.cloud_params)
            rep.t_reshard = m.wall
        return rep

    @property
    def ready(self) -> bool:
        return self.edge_fn is not None

    def close(self) -> None:
        self.embed_fn = self.edge_fn = self.cloud_fn = self.head_fn = None
        self.params = None
        self.cloud_params = None
        self._cloud_psh = self._cloud_state_shardings = self._repl = None
        self._edge_sharding = None

    def reshard(self) -> int:
        """Place cloud weights AND the live cloud-range decode state onto
        this pipeline's placement (``PipelinePool.activate``'s
        mesh-transition hook); returns logical bytes actually moved.
        Weights were placed at build, so for a prebuilt standby only the
        decode state — which kept advancing on the old placement — moves
        here.  An unsharded pipeline taking over from a mesh build pulls
        the state back to its single device the same way."""
        if not self.ready:
            return 0
        moved = 0

        def place(tree, shardings):
            nonlocal moved
            leaves = jax.tree.leaves(tree)
            shards = jax.tree.leaves(shardings)
            if len(shards) == 1 and len(leaves) > 1:
                shards = shards * len(leaves)   # one sharding, whole tree
            if all(getattr(a, "sharding", None) == sh
                   for a, sh in zip(leaves, shards)):
                return tree, False
            moved += sum(np.prod(np.shape(a)) * np.dtype(a.dtype).itemsize
                         for a in leaves)
            placed = jax.device_put(tree, shardings)
            jax.block_until_ready(placed)
            return placed, True

        if self._cloud_psh is not None:
            self.cloud_params, _ = place(self.cloud_params, self._cloud_psh)
        state_sh = self._cloud_state_shardings
        if state_sh is None:
            state_sh = self._edge_sharding     # mesh -> single device
        s = self.session
        if hasattr(s, "replace_state") and state_sh is not None:
            cache = s.subset(self._u_edge, self._u_all)
            placed, changed = place(cache, state_sh)
            if changed:
                s.replace_state(placed)
        return int(moved)

    # -- serve -----------------------------------------------------------
    def _step(self, token, cache_edge, cache_cloud, pos):
        """One decode step through both stages; returns everything the
        session needs to commit, plus the measured stage timing."""
        edge_sh = self._edge_sharding
        if edge_sh is not None and \
                getattr(token, "sharding", None) != edge_sh:
            # the previous step's logits (hence this argmax token) may be
            # mesh-resident; the edge embed is compiled single-device
            token = jax.device_put(token, edge_sh)
        with timing.span("step.edge", timed=True) as m:
            x = self.embed_fn(self.params, token)
            xe, new_e, b_e = self.edge_fn(self.params, x, cache_edge, pos)
            timing.block(xe)
        t_edge = m.wall * self.edge_scale
        t_transfer = self.net.transfer_time(
            int(np.prod(xe.shape)) * xe.dtype.itemsize)
        with timing.span("step.cloud", timed=True) as m:
            new_c, b_c, logits = self._run_cloud(xe, cache_cloud, pos)
        t_cloud = m.wall
        if self._repl is not None:
            # mesh-resident and edge-resident bounds cannot mix in one
            # jnp.concatenate (device mismatch); the session stores numpy
            # anyway
            bounds = np.concatenate([timing.fetch(b_e), timing.fetch(b_c)],
                                    axis=0)
        else:
            bounds = jnp.concatenate([b_e, b_c], axis=0)
        return logits, {**new_e, **new_c}, bounds, \
            RequestTiming(t_edge, t_transfer, t_cloud)

    def _run_cloud(self, xe, cache_cloud, pos):
        """The cloud stage and the LM head, through their sync."""
        edge_sh = self._edge_sharding
        if self._cloud_state_shardings is not None:
            # the edge->cloud hop: AOT executables do not auto-reshard, so
            # the boundary token, position and any state entry not already
            # on the mesh (e.g. right after a recompute hand-off) are
            # placed explicitly — a no-op for already-placed steady state
            xe = jax.device_put(xe, self._repl)
            pos = jax.device_put(pos, self._repl)
            cache_cloud = jax.device_put(cache_cloud,
                                         self._cloud_state_shardings)
        elif edge_sh is not None and any(
                getattr(a, "sharding", None) != edge_sh
                for a in jax.tree.leaves(cache_cloud)):
            # single-device stage fed state left on a mesh (warm/serve
            # racing ahead of activation's reshard): pull it back
            cache_cloud = jax.device_put(cache_cloud, edge_sh)
            pos = jax.device_put(pos, edge_sh)
        xc, new_c, b_c = self.cloud_fn(self.cloud_params, xe, cache_cloud,
                                       pos)
        if self._repl is not None:
            # head is compiled for a replicated input; the decode stage's
            # output sharding is whatever GSPMD propagated
            xc = jax.device_put(xc, self._repl)
        logits = self.head_fn(self.cloud_params, xc)
        timing.block(logits)
        return new_c, b_c, logits

    def process(self, inputs=None, *, batch: int = 1, seq=None
                ) -> tuple:
        """Serve one decode request: advance the session by one token."""
        assert self.ready, "pipeline not built"
        s = self.session
        if s.pos >= self.runner.max_seq:
            raise RuntimeError(f"decode context full ({s.pos} >= "
                               f"max_seq {self.runner.max_seq})")
        with timing.span("step"):
            with timing.span("step.input"):
                token = None
                if isinstance(inputs, dict):
                    token = inputs.get("token")
                if token is None:
                    token = s.next_token()
                pos = s.step_pos()
                cache_edge = s.subset(0, self._u_edge)
                cache_cloud = s.subset(self._u_edge, self._u_all)
            logits, new, bounds, req = self._step(
                jnp.asarray(token, jnp.int32), cache_edge, cache_cloud, pos)
            with timing.span("step.commit"):
                s.commit_step(token, new, bounds, logits)
        return logits, req

    def warm(self, sample_inputs=None) -> RequestTiming:
        """Throwaway forward on SCRATCH state: absorbs the first-execution
        spike without advancing (or touching) the live session."""
        s = self.session
        zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
        tok = jnp.zeros((s.batch, 1), jnp.int32)
        _, _, _, req = self._step(
            tok, zeros(s.subset(0, self._u_edge)),
            zeros(s.subset(self._u_edge, self._u_all)),
            jnp.zeros_like(s.step_pos()))
        return req

    # -- memory accounting ------------------------------------------------
    def live_param_bytes(self) -> int:
        if not self.ready:
            return 0
        n = sum(a.size * a.dtype.itemsize
                for a in jax.tree.leaves(self.params))
        if self.cloud_params is not None \
                and self.cloud_params is not self.params:
            # mesh builds hold a second, sharded weight copy (logical
            # size; per-device it is 1/tp of this)
            n += sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(self.cloud_params))
        return n


# ---------------------------------------------------------------------------
# pool: hand-off executes at activation
# ---------------------------------------------------------------------------

@dataclass
class HandoffReport:
    """One executed state hand-off (what ``plan_handoff`` only priced)."""
    mode: str                 # 'transfer' | 'recompute' | 'none'
    moved_layers: int
    moved_bytes: int          # really-serialized bytes (transfer arm)
    t_wall: float             # measured on-thread seconds
    t_network: float          # priced link seconds (virtual, charged to
                              # the stream by the engine)
    plan: Optional[HandoffPlan]
    epoch: int                # session epoch the hand-off synced to
    fallback: bool = False    # transfer payload failed validation and the
                              # hand-off recovered via masked recompute

    @property
    def total(self) -> float:
        return self.t_wall + self.t_network


@guarded_by("_lock", "last_handoff", "handoffs")
class StatefulPipelinePool(PipelinePool):
    """PipelinePool over ``StatefulEdgeCloudPipeline``s.

    ``activate`` performs the state hand-off from the old active split to
    the new one before the pointer swap; the arm is the live plan's
    ``best`` unless ``force_mode`` pins it.  Entries carry the session
    epoch they were last synced at; a stale entry is re-synced at swap —
    the standby's compiled stages are reused, its view of the context is
    not."""

    def __init__(self, runner: StatefulStageRunner, net: NetworkModel,
                 sample_inputs, *, session: DecodeSession,
                 force_mode: Optional[str] = None, **kwargs):
        super().__init__(runner, net, sample_inputs, **kwargs)
        self.session = session
        self.force_mode = force_mode
        self.last_handoff: Optional[HandoffReport] = None
        self.handoffs: List[HandoffReport] = []

    def _new_pipeline(self, key) -> StatefulEdgeCloudPipeline:
        return StatefulEdgeCloudPipeline(self.runner, key.split, self.net,
                                         session=self.session,
                                         owns_weights=key.owns_weights,
                                         mesh_shape=key.mesh_shape)

    # -- hand-off ---------------------------------------------------------
    def _execute_handoff(self, old_split: int, new_split: int
                         ) -> HandoffReport:
        s = self.session
        if s.pos == 0 or old_split == new_split:
            return HandoffReport("none", 0, 0, 0.0, 0.0, None, s.epoch)
        plan = plan_handoff(s.cfg, old_split=old_split, new_split=new_split,
                            seq_len=s.pos, batch=s.batch,
                            net=s.handoff_net(self.net),
                            target=s.calib_spec, act_bytes=4)
        mode = self.force_mode or plan.best
        lo, hi = min(old_split, new_split), max(old_split, new_split)
        fallback = False
        t_wall = 0.0                # the walls of the handoff.* spans
        if mode == "transfer":
            with timing.span("handoff.export", timed=True, mode=mode,
                             layers=hi - lo) as m:
                payload, nbytes = s.export_layers(lo, hi)
                fplan = self.fault_plan
                if fplan is not None:
                    # chaos valve: in-transit corruption/truncation
                    fplan.mutate_handoff(payload, epoch=s.epoch)
            t_wall += m.wall
            # the (possibly corrupt) payload really crossed the link, so
            # its priced seconds stand even when validation rejects it
            t_network = self.net.transfer_time(nbytes)
            try:
                with timing.span("handoff.import", timed=True, mode=mode,
                                 layers=hi - lo, bytes=nbytes) as m:
                    s.import_layers(payload)
            except HandoffCorrupted as e:
                warnings.warn(f"hand-off payload failed validation ({e}); "
                              f"recovering via masked recompute",
                              HandoffIntegrityWarning)
                mode, fallback = "recompute", True
            t_wall += m.wall
        else:
            nbytes, t_network = 0, 0.0
        if mode != "transfer":      # chosen, forced, or the fallback
            with timing.span("handoff.recompute", timed=True, mode=mode,
                             layers=hi - lo) as m:
                s.recompute_layers(lo, hi)
            t_wall += m.wall
        return HandoffReport(mode, hi - lo, nbytes, t_wall, t_network,
                             plan, s.epoch, fallback=fallback)

    def take_last_handoff(self) -> Optional[HandoffReport]:
        """Pop the hand-off the most recent activation executed (the
        ``SwitchReport``-stamping contract of ``strategies.apply_handoff``)."""
        with self._lock:
            h, self.last_handoff = self.last_handoff, None
        return h

    # -- overridden lifecycle ---------------------------------------------
    def activate(self, key) -> float:
        """Hand-off + pointer swap.  The returned ``t_switch`` INCLUDES
        the hand-off's measured wall, so every strategy's own downtime /
        t_blocked accounting sees it exactly once — the priced link
        seconds (virtual) are the only part left for
        ``strategies.apply_handoff`` to add.  (The base activation also
        executes + measures the mesh reshard when the key's mesh shape
        changed — ``StatefulEdgeCloudPipeline.reshard`` moves the live
        decode state along with any unplaced weights.)"""
        key = self._coerce_key(key)
        with self._lock:
            old_key = self.active_key if self.active_key is not None \
                else self._paused_key
            old_split = old_key.split if old_key is not None else None
            entry = self._entries[key]
            handoff = None
            if old_split is not None and (
                    old_split != entry.pipeline.split
                    or entry.state_epoch != self.session.epoch):
                # moved layers change sides; a stale same-split standby is
                # re-synced (a no-move hand-off) rather than trusted
                handoff = self._execute_handoff(old_split,
                                                entry.pipeline.split)
            t_switch = super().activate(key)
            entry.state_epoch = self.session.epoch
            if handoff is not None:
                self.last_handoff = handoff
                self.handoffs.append(handoff)
                t_switch += handoff.t_wall
        return t_switch


# ---------------------------------------------------------------------------
# convenience constructor
# ---------------------------------------------------------------------------

def make_stateful_manager(cfg: ArchConfig, params=None, *, split: int,
                          net: NetworkModel, prompt_len: int = 32,
                          batch: int = 1, max_seq: int = 128, seed: int = 0,
                          standby_split: Optional[int] = None,
                          warm_standbys: bool = False,
                          force_mode: Optional[str] = None,
                          mem_budget_bytes: Optional[int] = None,
                          decode_impl: str = "auto", rolled: bool = True):
    """A ``PipelineManager`` whose pool serves a stateful decode stream.

    Prefills a seeded prompt so the session state (and its hand-off
    surface) exists before the first pipeline builds.  Returns
    ``(manager, session)``.  ``decode_impl``/``rolled`` pin the runner's
    decode hot path (kernel routing, lax.scan-rolled ranges)."""
    from repro.core.switching import PipelineManager
    if params is None:
        params = T.init_model(cfg, jax.random.PRNGKey(seed))
    runner = StatefulStageRunner(cfg, params, max_seq=max_seq,
                                 decode_impl=decode_impl, rolled=rolled)
    session = DecodeSession(runner)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, prompt_len), 0, cfg.vocab_size)
    session.prefill(tokens)
    pool = StatefulPipelinePool(runner, net, {"tokens": tokens},
                                session=session, force_mode=force_mode,
                                warm_standbys=warm_standbys,
                                mem_budget_bytes=mem_budget_bytes)
    mgr = PipelineManager(runner, split, net, {"tokens": tokens},
                          pool=pool, standby_split=standby_split)
    return mgr, session
