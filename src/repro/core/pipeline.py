"""EdgeCloudPipeline: two compiled stages joined by a priced network link.

``process`` runs stage-edge (measured wall-clock), prices the boundary
transfer with the current NetworkModel (virtual time — there is no real
5 Mbps link in this container), and runs stage-cloud (measured wall-clock,
scaled by the cloud/edge speed ratio so a 1-core host still reproduces the
testbed's asymmetry).  Per-request breakdown mirrors Eq. 1.

``build`` is AOT: both stages compile via ``jit(...).lower(...).compile()``
against abstract avals (the boundary aval comes from an ``eval_shape``
trace, so no sample ever executes), and the edge and cloud compilations
run concurrently — XLA compilation releases the GIL, so the two stages
overlap and a build costs roughly max(stage) instead of
sum(trace+compile+execute) per stage.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax

from repro.core.hardware import CLOUD_SPEC, EDGE_SPEC
from repro.core import timing
from repro.core.network import NetworkModel
from repro.core.stages import StageRunner, abstractify, aval_fingerprint


def _parallel_build_default() -> bool:
    """Compile the two stages concurrently only when cores allow it.

    On <=2 cores the two XLA compilations just contend (each slows ~2x, so
    the wall time matches serial plus thread overhead); from 3 cores up the
    overlap is a real win.  ``NEUKONFIG_PARALLEL_BUILD=0/1`` overrides.
    """
    env = os.environ.get("NEUKONFIG_PARALLEL_BUILD")
    if env is not None:
        return env.strip().lower() not in ("0", "false", "no", "off", "")
    return (os.cpu_count() or 1) >= 3


PARALLEL_BUILD = _parallel_build_default()


@dataclass
class RequestTiming:
    t_edge: float
    t_transfer: float
    t_cloud: float

    @property
    def total(self) -> float:
        return self.t_edge + self.t_transfer + self.t_cloud


@dataclass
class BuildReport:
    t_weights: float = 0.0        # weight placement / reload
    t_compile_edge: float = 0.0
    t_compile_cloud: float = 0.0
    t_reshard: float = 0.0        # cloud-weight placement onto the mesh
    t_wall: float = 0.0           # the ``pool.build`` span's wall, set by
                                  # ``PipelinePool.ensure``; less than
                                  # ``total`` when the stages overlapped

    @property
    def total(self) -> float:
        return (self.t_weights + self.t_compile_edge + self.t_compile_cloud
                + self.t_reshard)


def tree_bytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def copy_to_device(tree):
    """A second device copy of ``tree`` through host memory (a new
    container's own weights), its traffic counted."""
    out = jax.tree.map(lambda a: jax.device_put(timing.fetch(a)), tree)
    timing.count("h2d_bytes", tree_bytes(tree))
    return out


class EdgeCloudPipeline:
    """One edge-cloud pipeline at a fixed split point.

    ``mesh_shape`` makes the CLOUD stage tensor-parallel: the cloud
    executable compiles against a ``jax.sharding.Mesh`` of that shape
    (``repro.launch.mesh.make_cloud_mesh``) with parameter shardings from
    ``repro.distributed.sharding.param_shardings`` and a mesh-resident
    weight copy placed at build time.  The edge stage stays single-device
    — the edge box has one accelerator; only the cloud gains devices.
    """

    def __init__(self, runner: StageRunner, split: int, net: NetworkModel,
                 *, edge_scale: float = CLOUD_SPEC.flops / EDGE_SPEC.flops,
                 owns_weights: bool = False,
                 mesh_shape: Optional[tuple] = None):
        self.runner = runner
        self.split = split
        self.net = net
        self.edge_scale = edge_scale     # edge is this much slower than host
        self.owns_weights = owns_weights  # True => separate weight buffers (2x mem)
        self.mesh_shape = tuple(mesh_shape) if mesh_shape else None
        self.edge_fn: Optional[Callable] = None
        self.cloud_fn: Optional[Callable] = None
        self.params = runner.params
        # the cloud stage's weight view: ``params`` when single-device, a
        # mesh-resident sharded copy when ``mesh_shape`` is set
        self.cloud_params = runner.params
        self._cloud_psh = None           # param shardings (mesh builds)
        self._cloud_in_shardings = None  # boundary-activation shardings
        # build-time input avals per stage; None = retracing jit path
        self._edge_avals = None
        self._cloud_avals = None

    # -- build ----------------------------------------------------------
    def build(self, sample_inputs, *, cold: bool, reload_from: Optional[str] = None
              ) -> BuildReport:
        """Compile both stages.

        cold=True  -> fresh closures (retrace+recompile): "new container".
        cold=False -> runner's cached jits: "same container" (hit if this
                      split was compiled before; otherwise compile only).
        reload_from -> reload weights from disk first (Pause-and-Resume:
                      the resumed app re-reads its model file).
        """
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

        lo_e, hi_e = 0, self.split + 1
        lo_c, hi_c = self.split + 1, r.num_units
        in_avals = abstractify(sample_inputs)
        edge_box: Dict[str, Any] = {}

        def _compile_edge():
            with timing.span("build.exec", timed=True, stage="edge") as m:
                try:
                    edge_box["fn"] = r.stage_executable(
                        lo_e, hi_e, self.params, in_avals, fresh=cold)
                except BaseException as e:
                    edge_box["error"] = e
            rep.t_compile_edge = m.wall

        # edge compiles on a helper thread while this thread derives the
        # boundary aval (an eval_shape trace — the sample never executes)
        # and compiles the cloud stage; XLA releases the GIL, so the two
        # compilations genuinely overlap when the host has cores to spare
        th = None
        if PARALLEL_BUILD:
            th = threading.Thread(target=timing.carry(_compile_edge),
                                  name="edge-stage-compile")
            th.start()
        with timing.span("build.exec", timed=True, stage="cloud") as m:
            mid_avals = r.stage_out_avals(lo_e, hi_e, self.params, in_avals)
            if self.mesh_shape is None:
                self.cloud_params = self.params
                self._cloud_psh = self._cloud_in_shardings = None
                cloud_fn = r.stage_executable(lo_c, hi_c, self.params,
                                              mid_avals, fresh=cold)
            else:
                from repro.launch.mesh import make_cloud_mesh
                mesh = make_cloud_mesh(self.mesh_shape)
                psh, ssh = r.stage_shardings(mesh, mid_avals)
                self._cloud_psh, self._cloud_in_shardings = psh, ssh
                cloud_fn = r.stage_executable(lo_c, hi_c, self.params,
                                              mid_avals, fresh=cold,
                                              shardings=(psh, ssh), mesh=mesh)
        rep.t_compile_cloud = m.wall
        if self.mesh_shape is not None:
            # the cloud container's weight copy lives ON the mesh; placing
            # it here (at build time) is what lets prebuilt standbys pay
            # the reshard off the stream
            with timing.span("build.reshard", timed=True) as m:
                self.cloud_params = jax.device_put(self.params, psh)
                timing.block(self.cloud_params)
            rep.t_reshard = m.wall
        if th is not None:
            th.join()
        else:
            _compile_edge()
        if "error" in edge_box:
            raise edge_box["error"]
        self.edge_fn, self.cloud_fn = edge_box["fn"], cloud_fn
        self._edge_avals = aval_fingerprint(in_avals)
        self._cloud_avals = aval_fingerprint(mid_avals)
        return rep

    def reshard(self) -> int:
        """Place any weight buffers not already on this pipeline's mesh.

        Called by ``PipelinePool.activate`` when a switch changes the
        cloud mesh shape; returns the logical bytes actually moved.  A
        pipeline built normally already placed its copy (``BuildReport.
        t_reshard``), so the on-stream cost is ~0 for prebuilt standbys —
        only an entry whose placement was dropped (or a subclass's live
        decode state) moves bytes here.
        """
        if not self.ready or self._cloud_psh is None:
            return 0
        leaves = jax.tree.leaves(self.cloud_params)
        shards = jax.tree.leaves(self._cloud_psh)
        if all(getattr(a, "sharding", None) == s
               for a, s in zip(leaves, shards)):
            return 0
        moved = sum(a.size * a.dtype.itemsize for a in leaves)
        self.cloud_params = jax.device_put(self.cloud_params, self._cloud_psh)
        jax.block_until_ready(self.cloud_params)
        return moved

    def warm(self, sample_inputs) -> RequestTiming:
        """One throwaway forward — the "always-running" warm-up.

        The first execution of a freshly compiled executable pays runtime
        setup (buffer donation plumbing, allocator growth) that an
        always-on container (the paper's Scenario-A standby) would have
        amortised long before a switch; run it at build time so it never
        lands on the first live request."""
        _, timing = self.process(sample_inputs)
        return timing

    @property
    def ready(self) -> bool:
        return self.edge_fn is not None

    def close(self) -> None:
        """Drop compiled stages + weight references (pool eviction)."""
        self.edge_fn = None
        self.cloud_fn = None
        self.params = None
        self.cloud_params = None
        self._cloud_psh = None
        self._cloud_in_shardings = None
        # a closed pipeline must surface its error, not retrace
        self._edge_avals = None
        self._cloud_avals = None

    # -- serve ------------------------------------------------------------
    def _run_edge(self, inputs):
        try:
            return self.edge_fn(self.params, inputs)
        except TypeError:
            # AOT executables are specialized to the build-time avals; iff
            # the fingerprints really differ, fall back to the retracing
            # warm path (and stay there — jit caches per shape from here
            # on).  Any other TypeError (closed pipeline, model bug)
            # propagates.  The check runs only on failure, so steady-state
            # serving pays nothing.
            if self._edge_avals is None \
                    or aval_fingerprint(inputs) == self._edge_avals:
                raise
            self._edge_avals = None
            self.edge_fn = self.runner.stage_fn(0, self.split + 1)
            return self.edge_fn(self.params, inputs)

    def _run_cloud(self, h):
        if self._cloud_in_shardings is not None:
            # the edge->cloud transfer: the boundary activation lands on
            # the cloud mesh (AOT executables do not auto-reshard inputs)
            h = jax.device_put(h, self._cloud_in_shardings)
        try:
            return self.cloud_fn(self.cloud_params, h)
        except TypeError:
            if self._cloud_avals is None \
                    or aval_fingerprint(h) == self._cloud_avals:
                raise
            self._cloud_avals = None
            self.cloud_fn = self.runner.stage_fn(self.split + 1,
                                                 self.runner.num_units)
            return self.cloud_fn(self.cloud_params, h)

    def process(self, inputs, *, batch: int = 1, seq: Optional[int] = None
                ) -> tuple[Any, RequestTiming]:
        assert self.ready, "pipeline not built"
        with timing.span("step"):
            with timing.span("step.edge", timed=True) as m:
                h = self._run_edge(inputs)
                timing.block(h)
            t_edge = m.wall * self.edge_scale
            if seq is None:
                seq = inputs["tokens"].shape[1] if "tokens" in inputs else 1
            bbytes = self.runner.boundary_bytes(self.split, batch, seq)
            t_transfer = self.net.transfer_time(bbytes)
            with timing.span("step.cloud", timed=True) as m:
                out = self._run_cloud(h)
                timing.block(out)
            t_cloud = m.wall
        return out["logits"], RequestTiming(t_edge, t_transfer, t_cloud)

    # -- memory accounting (Table I) --------------------------------------
    def live_param_bytes(self) -> int:
        if not self.ready:
            return 0
        n = sum(a.size * a.dtype.itemsize
                for a in jax.tree.leaves(self.params))
        if self.cloud_params is not None and self.cloud_params is not self.params:
            # mesh builds hold a second, sharded weight copy (logical size;
            # per-device it is 1/tp of this)
            n += sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(self.cloud_params))
        return n
