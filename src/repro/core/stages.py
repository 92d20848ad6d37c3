"""Stage-wise model execution — the "sequence of layers" abstraction.

A model is a list of UNITS: unit 0 = embedding (+frontend/encoder), units
1..L = decoder layers, unit L+1 = LM head.  A split after unit ``k`` puts
units [0, k] on the edge stage and (k, N) on the cloud stage; the boundary
tensor is the hidden state (plus, for whisper, the encoder context — the
encoder itself is ONE unit, mirroring the paper's rule that parallel paths
are not split).

``StageRunner.stage_fn(lo, hi)`` returns a jitted callable for the unit
range; the cached variant is the Dynamic-Switching "same container"
(warm) path, while ``fresh_stage_fn`` deliberately builds a new closure so
jit must retrace+recompile — the "new container" (cold) path.

``stage_executable`` is the AOT fast path: ``jax.jit(...).lower(...)
.compile()`` against abstract input avals, so a stage compiles without
ever executing a sample, and the resulting executable is cached per
``(lo, hi, avals)`` and shared across every pool entry (warm builds never
retrace).  ``fresh=True`` bypasses the shared cache both ways — the
deliberate cold "new container" semantics.  All caches are lock-guarded:
background build threads and the serving thread compile concurrently.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.concurrency import RANK_STAGE_CACHE, guarded_by, make_lock
from repro.models import layers as Lyr
from repro.models import ssm as SSM
from repro.models import transformer as T


def _layer_at(params, i):
    return jax.tree.map(lambda a: a[i], params["layers"])


def abstractify(tree):
    """Pytree of concrete arrays -> pytree of ShapeDtypeStructs."""
    return jax.tree.map(
        lambda a: a if isinstance(a, jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct(np.shape(a), jnp.result_type(a)), tree)


def aval_fingerprint(tree) -> Tuple:
    """Hashable identity of a pytree's avals (structure + shapes + dtypes)."""
    leaves, treedef = jax.tree_util.tree_flatten(abstractify(tree))
    return (str(treedef),) + tuple((tuple(l.shape), str(l.dtype))
                                   for l in leaves)


@guarded_by("_cache_lock", "_jit_cache", "_aot_cache", "_aval_cache",
            rank=RANK_STAGE_CACHE, init_methods=("_init_stage_caches",))
class _CompiledStageCache:
    """Warm-path stage compilation shared by every stage-runner flavour.

    Hosts three thread-safe caches: jitted callables (legacy warm path),
    per-(range, avals) output avals (cheap ``eval_shape`` traces), and
    per-(range, avals) AOT executables (the no-retrace pool fast path).
    """

    def _init_stage_caches(self) -> None:
        self._jit_cache: Dict[Tuple[int, int], Any] = {}
        self._aot_cache: Dict[Tuple, Any] = {}
        self._aval_cache: Dict[Tuple, Any] = {}
        self._cache_lock = make_lock("stage-cache", RANK_STAGE_CACHE)

    def stage_fn(self, lo: int, hi: int):
        """Warm path: cached jitted callable (Dynamic Switching, same
        container)."""
        key = (lo, hi)
        with self._cache_lock:
            if key not in self._jit_cache:
                self._jit_cache[key] = jax.jit(self._make_fn(lo, hi))
            return self._jit_cache[key]

    def fresh_stage_fn(self, lo: int, hi: int):
        """Cold path: new closure => jit retrace+recompile (new container)."""
        return jax.jit(self._make_fn(lo, hi))

    def stage_out_avals(self, lo: int, hi: int, params, state):
        """Output avals of units [lo, hi) for the given input avals — an
        abstract trace (``eval_shape``), never an execution."""
        in_avals = abstractify(state)
        key = (lo, hi) + aval_fingerprint(in_avals)
        with self._cache_lock:
            hit = self._aval_cache.get(key)
        if hit is not None:
            return hit
        out = jax.eval_shape(self._make_fn(lo, hi), abstractify(params),
                             in_avals)
        with self._cache_lock:
            self._aval_cache[key] = out
        return out

    def stage_executable(self, lo: int, hi: int, params, state, *,
                         fresh: bool = False, shardings=None, mesh=None):
        """AOT-compiled executable for units [lo, hi), specialized to the
        avals of ``(params, state)``.

        ``fresh=False`` consults/populates the shared executable cache so a
        configuration seen before costs nothing; ``fresh=True`` always
        retraces and recompiles and leaves no trace in the cache ("new
        container").  Compilation happens via ``lower().compile()`` against
        abstract avals: no sample ever executes.

        ``shardings`` (a ``(param_shardings, state_shardings)`` pair from
        ``stage_shardings``) + ``mesh`` compile the stage SPMD over the
        device mesh — the sharded cloud stage.  The mesh identity enters
        the cache key so sharded and single-device executables for the
        same range never collide; tracing runs under the activation-
        sharding policy (``repro.distributed.policy``) so GSPMD gets the
        same constraints the production dry-run proves out.
        """
        in_avals = abstractify(state)
        mesh_key = None if mesh is None else \
            (tuple(mesh.axis_names), tuple(mesh.devices.shape))
        key = (lo, hi, mesh_key) + aval_fingerprint(in_avals)
        if not fresh:
            with self._cache_lock:
                hit = self._aot_cache.get(key)
            if hit is not None:
                return hit
        compiled = self._compile_stage(lo, hi, params, in_avals,
                                       shardings=shardings, mesh=mesh)
        if not fresh:
            with self._cache_lock:
                self._aot_cache[key] = compiled
        return compiled

    def _compile_stage(self, lo: int, hi: int, params, in_avals, *,
                       shardings=None, mesh=None):
        if mesh is None or shardings is None:
            return jax.jit(self._make_fn(lo, hi)).lower(
                params, in_avals).compile()
        from repro.distributed import policy as pol
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        tp_size = sizes.get("model", 1)
        dp_axes = tuple(a for a in ("pod", "data") if a in sizes)
        dp = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
        dp_size = int(np.prod([sizes[a] for a in dp_axes])) if dp_axes else 1
        attn = "heads"
        if getattr(self.cfg, "num_kv_heads", None):
            attn = pol.choose_attn_mode(self.cfg, tp_size, kind="prefill")
        # process-global policy state: benign for concurrent unsharded
        # traces (their bare-P constraints have no mesh and are dropped),
        # and sharded builds are serialized by the pool's single worker
        with mesh, \
                pol.policy(dp=dp, tp="model", attn=attn, tp_size=tp_size,
                           dp_size=dp_size, active=True):
            return jax.jit(self._make_fn(lo, hi),
                           in_shardings=shardings).lower(
                params, in_avals).compile()


class StageRunner(_CompiledStageCache):
    """Executes unit ranges [lo, hi) of a model for full-seq inference."""

    def __init__(self, cfg: ArchConfig, params, attn_impl: str = "chunked"):
        self.cfg = cfg
        self.params = params
        self.attn_impl = attn_impl
        self._init_stage_caches()

    # -- unit layout --------------------------------------------------
    @property
    def num_units(self) -> int:
        return self.cfg.num_layers + 2

    def edge_param_bytes(self, split: int) -> int:
        """Approximate parameter bytes the edge holds at ``split`` (layers
        ``[0, split)`` plus the embedding): the layer-proportional share
        of the full model.  The degraded-mode picker uses this to find
        the deepest edge-only split that fits ``mem_budget_bytes``."""
        total = sum(int(a.size) * a.dtype.itemsize
                    for a in jax.tree.leaves(self.params))
        frac = (split + 1) / (self.cfg.num_layers + 2)
        return int(total * frac)

    # -- execution ----------------------------------------------------
    def _apply_unit(self, state: Dict[str, Any], i: int) -> Dict[str, Any]:
        cfg, params = self.cfg, self.params
        if i == 0:
            x = T.embed_inputs(cfg, params, state)
            if cfg.family == "audio":
                x = x + Lyr.sinusoidal_positions(
                    x.shape[1], cfg.d_model).astype(x.dtype)[None]
                enc = T.encode_audio(cfg, params, state["frames"],
                                     attn_impl=self.attn_impl, remat=False)
                return {"h": x, "enc": enc}
            return {"h": x, "x0": x} if cfg.family == "hybrid" else {"h": x}
        if i == self.num_units - 1:
            x = T._apply_norm(cfg, params["final_norm"], state["h"])
            logits = (x @ T.lm_head_weights(cfg, params)).astype(jnp.float32)
            return {"logits": logits}
        # decoder layer i-1
        li = i - 1
        x = state["h"]
        rope_cs = T._rope_for(cfg, x.shape[1])
        window = cfg.sliding_window
        fam = cfg.family
        if fam in ("dense", "moe", "vlm", "audio"):
            lp = _layer_at(params, li)
            x, _, _ = T.attn_block_full(cfg, lp, x, rope_cs,
                                        impl=self.attn_impl, window=window)
            if fam == "audio":
                ckv = T._enc_cross_kv(cfg, lp, state["enc"])
                x = T.cross_block_full(cfg, lp, x, ckv, impl=self.attn_impl)
        elif fam == "ssm":
            lp = _layer_at(params, li)
            h = T._apply_norm(cfg, lp["ln"], x)
            y, _ = SSM.mamba1_block(lp["mamba"], h, cfg=cfg)
            x = x + y
        elif fam == "hybrid":
            t = None
            if li in cfg.app_layers:
                def attend(q, k, v):
                    return Lyr.attention(q, k, v, causal=True, window=window,
                                         impl=self.attn_impl), None
                t, _ = T.hybrid_block(cfg, params, cfg.app_layers.index(li),
                                      x, state["x0"], rope_cs, attend)
            x, _ = T.hybrid_layer(cfg, _layer_at(params, li), x, t)
        else:
            raise ValueError(fam)
        out = dict(state)
        out["h"] = x
        return out

    def run_units(self, state, lo: int, hi: int):
        for i in range(lo, hi):
            state = self._apply_unit(state, i)
        return state

    # -- compiled stage functions --------------------------------------
    def _make_fn(self, lo: int, hi: int):
        def fn(params, state):
            runner = StageRunner(self.cfg, params, self.attn_impl)
            return runner.run_units(state, lo, hi)
        return fn

    # -- sharded (tensor-parallel) cloud stage -------------------------
    def stage_shardings(self, mesh, state):
        """``(param_shardings, state_shardings)`` for compiling a stage
        over ``mesh``.

        Parameters follow ``repro.distributed.sharding.param_shardings``
        (heads / d_ff / experts / vocab -> the "model" axis).  The
        boundary activation is REPLICATED: the edge ships one hidden
        state to the cloud and every tensor-parallel shard consumes it
        whole — batch sharding would need dp >= batch, which serving's
        batch-of-1 streams never have.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.sharding import param_shardings
        psh = param_shardings(self.cfg, mesh, abstractify(self.params),
                              shard_fsdp=False)
        replicated = NamedSharding(mesh, P())
        ssh = jax.tree.map(lambda _: replicated, abstractify(state))
        return psh, ssh

    def boundary_bytes(self, split: int, batch: int, seq: int,
                       act_bytes: int = 4) -> int:
        """Bytes crossing the link for a split after unit `split`."""
        cfg = self.cfg
        n = batch * seq * cfg.d_model * act_bytes
        if cfg.family == "hybrid" and any(i >= split
                                          for i in cfg.app_layers):
            n *= 2                  # x0 beside x while an application waits
        if cfg.family == "audio":
            n += batch * cfg.encoder.context_len * cfg.d_model * act_bytes
        return n


class CnnStageRunner(_CompiledStageCache):
    """StageRunner-compatible executor for the paper's own CNN models
    (video-analytics workload, Figs. 2-3): unit i = conv/pool/block/dense
    layer; boundary activations VARY with depth, so the optimal split
    actually moves with bandwidth."""

    def __init__(self, cfg, key=None, params=None):
        import jax as _jax
        from repro.models import cnn as _cnn
        self.cfg = cfg
        key = key if key is not None else _jax.random.PRNGKey(0)
        if params is None:
            params, units, shapes = _cnn.build_cnn(cfg, key)
        else:
            _, units, shapes = _cnn.build_cnn(cfg, key)
        self.params, self.units, self.shapes = params, units, shapes
        self._cnn = _cnn
        self._init_stage_caches()

    @property
    def num_units(self) -> int:
        return len(self.units)

    def _make_fn(self, lo: int, hi: int):
        units = self.units
        last = hi == len(units)

        def fn(params, state):
            x = state["h"] if "h" in state else state["image"]
            for i in range(lo, hi):
                x = units[i][1](params[i], x)
            return {"logits": x} if last else {"h": x}
        return fn

    def boundary_bytes(self, split: int, batch: int, seq: int = 1,
                       act_bytes: int = 4) -> int:
        import numpy as _np
        return int(_np.prod(self.shapes[split])) * batch * act_bytes
