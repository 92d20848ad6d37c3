"""Per-layer profiling: the data behind Eq. 1 (T_inf = T_e + T_t + T_c).

The paper profiles every layer's compute time on edge and cloud plus the
boundary activation size (section II-A).  We support both of the paper's
cited methods:

* measured  — run each unit on this host and time it (``profile_cnn``,
  ``profile_transformer_measured``) — the "real-time benchmarking" path [6];
* analytic  — FLOPs/spec estimation (``profile_transformer``) — the
  "estimation-based" path [18]; required for the 7B-76B archs that cannot
  execute on a laptop.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, CNNConfig
from repro.core.timing import Stopwatch
from repro.core.hardware import CLOUD_SPEC, EDGE_SPEC, ICI_LINK_BW, DeviceSpec
from repro.core.network import NetworkModel


@dataclass
class UnitProfile:
    name: str
    t_edge: float           # s, compute on edge
    t_cloud: float          # s, compute on cloud
    boundary_bytes: int     # activation bytes if we split AFTER this unit
    flops: float = 0.0


@dataclass
class ModelProfile:
    arch: str
    units: List[UnitProfile]
    # lazily-built prefix sums: (n, cum t_edge, cum t_cloud).  Makes
    # ``latency`` O(1) and therefore ``latency_curve``/``optimal_split``
    # O(n) instead of O(n²) — the partitioner re-solves Eq. 1 on every
    # network sample, so this is the controller's hot path.
    _psum: Optional[tuple] = field(default=None, init=False, repr=False,
                                   compare=False)
    # bumped by invalidate_cache(); downstream memos (e.g. switch_pool's
    # optimal_split cache) key on (profile, version, len(units))
    _version: int = field(default=0, init=False, repr=False, compare=False)
    # per-mesh latency model: mesh_shape -> (alpha, beta) scales on the
    # analytic terms (see ``mesh_cloud_time``); absent shape = (1.0, 1.0),
    # i.e. the uncalibrated roofline-style default.  Filled by
    # ``calibrate_mesh`` from measured sharded-cloud walls.
    mesh_models: Dict[Tuple[int, ...], Tuple[float, float]] = \
        field(default_factory=dict, repr=False, compare=False)

    def num_splits(self) -> int:
        return len(self.units) - 1  # split after unit i, i in [0, n-2]

    def cache_token(self) -> tuple:
        """Identity for memos over this profile's current timing data."""
        return (id(self), self._version, len(self.units))

    def _prefix(self) -> tuple:
        n = len(self.units)
        cached = self._psum
        if cached is not None and cached[0] == n:
            return cached
        pe = np.cumsum([u.t_edge for u in self.units])
        pc = np.cumsum([u.t_cloud for u in self.units])
        pb = np.cumsum([u.boundary_bytes for u in self.units])
        self._psum = (n, pe, pc, pb)
        return self._psum

    def invalidate_cache(self) -> None:
        """Call after mutating unit timings in place (adding/removing units
        is detected automatically)."""
        self._psum = None
        self._version += 1

    @staticmethod
    def mesh_tp(mesh_shape) -> int:
        """Tensor-parallel degree of a cloud mesh shape (last axis; a
        leading data axis cannot help a batch-of-1 serving stream)."""
        return int(mesh_shape[-1]) if mesh_shape else 1

    def mesh_model(self, mesh_shape) -> Tuple[float, float]:
        """Calibration scales ``(alpha, beta)`` for a mesh shape: alpha
        multiplies the 1/tp compute term, beta the ring-collective term."""
        if mesh_shape is None:
            return (1.0, 1.0)
        return self.mesh_models.get(tuple(mesh_shape), (1.0, 1.0))

    def mesh_cloud_time(self, t_cloud: float, coll_bytes: float,
                        mesh_shape) -> float:
        """Per-mesh cloud-stage time — the per-unit cost as a function of
        mesh shape.  The uncalibrated default is the roofline 3-term
        shape restricted to what tensor parallelism changes:

            t = alpha * t_cloud / tp                       (compute, 1/tp)
              + beta * 2(tp-1)/tp * coll_bytes / link_bw   (ring all-reduce)

        with the same ``ICI_LINK_BW`` constant ``repro.distributed.
        roofline`` prices collectives with — which is exactly what makes
        the model checkable against measured ``Roofline`` terms.
        ``coll_bytes`` is the summed per-unit activation volume of the
        cloud range (each TP layer all-reduces its residual-stream
        partials).
        """
        tp = self.mesh_tp(mesh_shape)
        if tp <= 1:
            return t_cloud
        alpha, beta = self.mesh_model(mesh_shape)
        t_coll = 2.0 * (tp - 1) / tp * float(coll_bytes) / ICI_LINK_BW
        return alpha * t_cloud / tp + beta * t_coll

    def latency(self, split: int, net: NetworkModel, mesh_shape=None):
        """(T_e, T_t, T_c) for a split after unit `split` (Eq. 1).

        ``mesh_shape`` prices the CLOUD side on a tensor-parallel mesh of
        that shape via the per-mesh latency model (``mesh_cloud_time``).
        """
        n, pe, pc, pb = self._prefix()
        t_e = float(pe[split])
        t_c = float(pc[n - 1] - pc[split])
        if mesh_shape is not None:
            coll = float(pb[n - 1] - pb[split])
            t_c = self.mesh_cloud_time(t_c, coll, mesh_shape)
        t_t = net.transfer_time(self.units[split].boundary_bytes)
        return t_e, t_t, t_c

    def total_latency(self, split: int, net: NetworkModel,
                      mesh_shape=None) -> float:
        return sum(self.latency(split, net, mesh_shape))


# ---------------------------------------------------------------------------
# measured profiling (CNNs + reduced transformers)
# ---------------------------------------------------------------------------

def _time_fn(fn, *args, reps=3) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    sw = Stopwatch()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return sw.elapsed() / reps


def profile_cnn(cfg: CNNConfig, params, units, shapes, *, batch=1,
                edge=EDGE_SPEC, cloud=CLOUD_SPEC, dtype=jnp.float32,
                reps=3) -> ModelProfile:
    """Measured per-unit times on this host, scaled to edge/cloud specs.

    The host measurement fixes the *relative* per-layer cost; the edge/cloud
    specs set absolute scale (host flops assumed = cloud spec).
    """
    from repro.models import cnn as cnn_mod
    x = jnp.zeros((batch, cfg.input_hw, cfg.input_hw, cfg.input_ch), dtype)
    out_profiles = []
    scale_edge = cloud.flops / edge.flops
    for i, (name, fn) in enumerate(units):
        jf = jax.jit(lambda p, x, fn=fn: fn(p, x))
        t = _time_fn(jf, params[i], x, reps=reps)
        bbytes = int(np.prod(shapes[i])) * batch * np.dtype(np.float32).itemsize
        out_profiles.append(UnitProfile(name, t * scale_edge, t, bbytes))
        x = fn(params[i], x)
    return ModelProfile(cfg.name, out_profiles)


# ---------------------------------------------------------------------------
# analytic profiling (full-size transformers)
# ---------------------------------------------------------------------------

def _layer_flops(cfg: ArchConfig, kind: str, tokens: int, seq: int) -> float:
    """Forward FLOPs of one decoder layer over `tokens` tokens."""
    d = cfg.d_model
    if kind == "attn":
        hd, H, KH = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
        proj = 2 * tokens * d * hd * (2 * H + 2 * KH)
        ctx = min(seq, cfg.sliding_window or seq)
        att = 2 * 2 * tokens * ctx * H * hd   # QK^T + PV (upper bound, causal)
        if cfg.moe is not None:
            m = cfg.moe
            ffn = 2 * tokens * 3 * d * (m.top_k * m.expert_d_ff
                                        + (m.shared_d_ff if m.num_shared_experts else 0))
        else:
            n_mats = 3 if cfg.gated_mlp else 2
            ffn = 2 * tokens * n_mats * d * cfg.d_ff
        return proj + att + ffn
    if kind == "mamba1":
        di, s = cfg.d_inner, cfg.ssm
        return 2 * tokens * (d * 2 * di + di * (s.dt_rank + 2 * s.d_state)
                             + s.dt_rank * di + di * d) \
            + 6 * tokens * di * s.d_state
    if kind == "mamba2":
        di, s = cfg.d_inner, cfg.ssm
        H = di // s.head_dim
        return 2 * tokens * d * (2 * di + 2 * s.n_groups * s.d_state + H) \
            + 2 * tokens * di * d + 6 * tokens * di * s.d_state
    if kind == "shared":            # one application of a hybrid's block
        w, F, r = cfg.num_heads * cfg.head_dim, cfg.d_ff, cfg.adapter_rank
        ctx = min(seq, cfg.sliding_window or seq)
        return 2 * tokens * (3 * 2 * d * w + w * d + 3 * d * F
                             + d * r + r * 2 * F + d * d) \
            + 4 * tokens * ctx * w
    raise ValueError(kind)


def profile_transformer(cfg: ArchConfig, *, seq: int, batch: int = 1,
                        edge: DeviceSpec = EDGE_SPEC,
                        cloud: DeviceSpec = CLOUD_SPEC,
                        act_bytes: int = 2) -> ModelProfile:
    """Analytic Eq.-1 profile.  Units: [embed] + decoder layers + [head].

    Boundary bytes between decoder layers are batch*seq*d_model*act_bytes —
    constant for transformers, which is itself a finding (section 4 of
    DESIGN.md): the optimal split for a uniform-width transformer is driven
    purely by compute balance, unlike VGG (Fig. 2) where activation volume
    varies 100x across layers.
    """
    tokens = batch * seq
    bbytes = batch * seq * cfg.d_model * act_bytes
    apps = cfg.app_layers if cfg.family == "hybrid" else ()

    def out_bytes(i):
        # the hybrid's x0 stream crosses beside x while an application
        # lies past the boundary after unit i (i = -1: the embedding)
        return bbytes * (2 if any(a > i for a in apps) else 1)

    units = [UnitProfile("embed", 0.0, 0.0, out_bytes(-1), 0.0)]
    for i, kind in enumerate(cfg.layer_kinds()):
        fl = _layer_flops(cfg, kind, tokens, seq)
        if i in apps:               # the application runs inside layer i
            fl += _layer_flops(cfg, "shared", tokens, seq)
        units.append(UnitProfile(
            f"{kind}{i}",
            fl / (edge.flops * edge.mfu),
            fl / (cloud.flops * cloud.mfu),
            out_bytes(i), fl))
    head_fl = 2 * tokens * cfg.d_model * cfg.vocab_size
    units.append(UnitProfile("head", head_fl / (edge.flops * edge.mfu),
                             head_fl / (cloud.flops * cloud.mfu), 0, head_fl))
    return ModelProfile(cfg.name, units)


# ---------------------------------------------------------------------------
# measured-decode calibration
# ---------------------------------------------------------------------------

def calibrate_decode(profile: ModelProfile, timings: Sequence, *,
                     split: int) -> Tuple[float, float]:
    """Rescale per-unit timings so Eq.-1 pricing matches MEASURED decode.

    ``timings`` are measured per-token stage walls from the serving path
    (any objects with ``t_edge``/``t_cloud`` attributes, e.g. the
    ``RequestTiming``s that ``StatefulEdgeCloudPipeline.process``
    returns), taken at a known ``split`` — the same split-after-unit
    index ``latency``/``optimal_split`` use (for a stateful pipeline at
    layer split ``s`` that is ``s``).
    The medians fix the absolute scale of the edge and cloud sides; the
    analytic profile keeps fixing the *relative* per-layer shape.  This
    is what lets ``optimal_split`` price the kernel-routed decode path
    (``decode_impl="kernel"``) instead of whatever spec sheet the
    analytic profile assumed: after a decode-path speedup the measured
    walls shrink, the profile shrinks with them, and the split optimum
    moves accordingly.

    Mutates ``profile`` in place (``invalidate_cache`` is called, so
    memoized ``optimal_split`` results are correctly dropped) and
    returns the applied ``(edge_scale, cloud_scale)``."""
    def med(xs):
        return float(np.median(np.asarray(xs, np.float64)))
    t_edge = med([t.t_edge for t in timings])
    t_cloud = med([t.t_cloud for t in timings])
    n, pe, pc, _ = profile._prefix()
    pred_e = float(pe[split])
    pred_c = float(pc[n - 1] - pc[split])
    scale_e = t_edge / pred_e if pred_e > 0 and t_edge > 0 else 1.0
    scale_c = t_cloud / pred_c if pred_c > 0 and t_cloud > 0 else 1.0
    for u in profile.units:
        u.t_edge *= scale_e
        u.t_cloud *= scale_c
    profile.invalidate_cache()
    return scale_e, scale_c


def calibrate_mesh(profile: ModelProfile, timings: Sequence, *, split: int,
                   mesh_shape) -> Tuple[float, float]:
    """Fit the per-mesh latency model to MEASURED sharded-cloud walls.

    The mirror of ``calibrate_decode`` for the mesh axis: ``timings`` are
    measured stage walls (objects with a ``t_cloud`` attribute) from a
    pipeline whose cloud stage ran on a mesh of ``mesh_shape`` at the
    given ``split``.  One measurement point fits one scale: alpha and
    beta move together by measured/predicted, preserving the analytic
    compute/collective ratio (two mesh shapes would over-determine a
    single (alpha, beta) pair; per-shape entries keep each shape's fit
    independent).  Stores the scales on ``profile.mesh_models`` and
    bumps the cache version so memoized ``optimal_split`` results drop.
    """
    if mesh_shape is None or ModelProfile.mesh_tp(mesh_shape) <= 1:
        return (1.0, 1.0)
    mesh_shape = tuple(int(d) for d in mesh_shape)
    t_cloud = float(np.median(np.asarray([t.t_cloud for t in timings],
                                         np.float64)))
    n, pe, pc, pb = profile._prefix()
    base_c = float(pc[n - 1] - pc[split])
    coll = float(pb[n - 1] - pb[split])
    # predict with the CURRENT scales, then apply the correction ratio
    pred = profile.mesh_cloud_time(base_c, coll, mesh_shape)
    scale = t_cloud / pred if pred > 0 and t_cloud > 0 else 1.0
    alpha, beta = profile.mesh_model(mesh_shape)
    profile.mesh_models[mesh_shape] = (alpha * scale, beta * scale)
    profile.invalidate_cache()
    return profile.mesh_models[mesh_shape]
