"""Production mesh definitions (TPU v5e).

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model) — the pod axis
composes with data parallelism (batch sharded over pod x data) and with
FSDP weight sharding; the dry-run proves every architecture lowers with it.

A FUNCTION (not a module constant) so importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke paths."""
    return jax.make_mesh((1, 1), ("data", "model"))


def make_cloud_mesh(shape):
    """The serving CLOUD stage's mesh: last axis is tensor-parallel
    ("model"), a leading axis (if any) is "data".

    Works over whatever devices the process has — real accelerators in
    production, CPU fake devices in CI (run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``; ``ci.sh``
    arranges this for ``tests/test_sharding.py``).  Raises with an
    actionable message when the host has fewer devices than the shape
    needs, instead of letting ``jax.make_mesh`` fail obscurely.
    """
    shape = tuple(int(d) for d in shape)
    if not shape or any(d < 1 for d in shape):
        raise ValueError(f"bad mesh shape {shape!r}")
    if len(shape) > 2:
        raise ValueError(f"cloud mesh is at most (data, model); got {shape!r}")
    need = 1
    for d in shape:
        need *= d
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"cloud mesh {shape} needs {need} devices, host has {have} "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"before importing jax for CPU fake devices)")
    axes = ("model",) if len(shape) == 1 else ("data", "model")
    # Auto axes: GSPMD propagates shardings through the stage, the
    # per-shard kernels' outputs included (``kernels.ops``); Explicit
    # axes would type every intermediate and refuse the mixed layouts
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))
