"""Four-chip smoke run of the sharded cloud stage on a TPU, through the
calls a user makes.

    python chip_smoke.py              # on a host with four chips

It puts the cloud stage of an 8-layer qwen2.5-3b on a (4,) mesh,
compares it with a single-device cloud, then moves it to a (2,) mesh.
No cell of the chip benchmark (``BENCHMARK.json``, ``chipbench/``) runs
a mesh yet; every one-chip path is measured there.

Every number printed is smoke output from one run, not a benchmark
metric.  The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``; a failed check, a host with fewer
than four devices, or a run where JAX finds no TPU (exit 2) exits
non-zero without it.
"""
import argparse
import dataclasses
import functools
import json
import sys
import time
import warnings
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import BackgroundBuildFailed, NetworkModel  # noqa: E402
from repro.core.pool import SwitchAbortedWarning  # noqa: E402
from repro.core.stateful import HandoffIntegrityWarning  # noqa: E402
from repro.core.strategies import StandbySplitMismatch  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving import make_session_manager  # noqa: E402

CHIPS = 4         # the sharded phase puts the cloud stage on a (4,) mesh
# Sharded vs single-device cloud, both compiled at "highest" matmul
# precision: float32 throughout, and only the all-reduce changes the
# order of the sums.  (At the default precision the two programs round
# to bf16 at different points and differ by up to 3e-3 of max|logit|,
# which would hide a sharding error of that size.)
SHARD_TOL = 1e-4
SEED = 0          # random weights and prompts both come from it


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def rel_err(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def device_line() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_kernels(runner, *executables) -> None:
    """Decode ran through the Pallas kernels, compiled for the chip."""
    check(runner.resolved_decode_impl == "kernel",
          f"decode_impl resolved to {runner.resolved_decode_impl!r}")
    for exe in executables:
        check("tpu_custom_call" in exe.as_text(),
              "a decode executable holds no Pallas kernel")


def gib(n) -> str:
    return f"{n / 2**30:.3f} GiB"


# ---------------------------------------------------------------------------
# the sharded cloud stage
# ---------------------------------------------------------------------------

def phase_sharded(cfg, *, split: int, steps: int = 4) -> None:
    params = jax.jit(functools.partial(T.init_model, cfg))(
        jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(8, 65))).astype(np.int32)
               for _ in range(4)]

    def session_pool():
        mgr, sm = make_session_manager(cfg, params, split=split,
                                       net=NetworkModel(20.0), num_slots=4,
                                       max_seq=256, seed=SEED)
        for i, p in enumerate(prompts):
            sm.admit(p, sid=f"s{i}")
        return mgr, sm

    mgr, _ = session_pool()
    try:
        ref = [np.asarray(mgr.serve({})[0]) for _ in range(2 * steps)]
    finally:
        mgr.close()
    print(f"[sharded] {cfg.name} cut to {cfg.num_layers} layers (kv heads "
          f"{cfg.num_kv_heads}, vocab {cfg.vocab_size}), split {split}, "
          f"matmul precision highest: single-device cloud decoded "
          f"{2 * steps} steps", flush=True)

    mgr, sm = session_pool()
    try:
        out = []
        for mesh, n in (((4,), steps), ((2,), steps)):
            mgr.set_mesh_shape(mesh)
            rep = mgr.repartition("switch_b2", split)
            check(rep.new_mesh == mesh, f"cloud mesh {rep.new_mesh}")
            pipe = mgr.pool.active
            require_kernels(sm.runner, pipe.cloud_fn)
            used = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                    for d in jax.devices()]
            print(f"[sharded] switch_b2 mesh {rep.old_mesh}->{rep.new_mesh}: "
                  f"reshard {rep.t_reshard * 1e3:.3f} ms, build "
                  f"{rep.t_build * 1e3:.3f} ms; bytes_in_use per device "
                  f"{[gib(u) for u in used]}", flush=True)
            if mesh == (4,):
                whole = sum(a.nbytes for a in jax.tree.leaves(params))
                check(min(used[1:4]) >= whole / 4 / 2,
                      "cloud weights are not on every chip")
            out += [np.asarray(mgr.serve({})[0]) for _ in range(n)]
    finally:
        mgr.close()
    errs = [rel_err(o, r) for o, r in zip(out, ref)]
    print(f"[sharded] logits vs single-device cloud, per step: "
          f"{[f'{e:.3g}' for e in errs]} of max|logit| (limit {SHARD_TOL})",
          flush=True)
    check(max(errs) <= SHARD_TOL, "sharded logits off the single-device cloud")


def main() -> int:
    argparse.ArgumentParser(description=__doc__.splitlines()[0]).parse_args()
    cache_dir = enable_compile_cache()
    dev = device_line()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX finds no TPU ({dev}); nothing was run",
              file=sys.stderr)
        return 2
    check(dev["count"] >= CHIPS,
          f"{CHIPS} chips needed, the host has {dev['count']} device(s)")
    for cat in (StandbySplitMismatch, SwitchAbortedWarning,
                BackgroundBuildFailed, HandoffIntegrityWarning):
        warnings.simplefilter("error", cat)
    print(f"chip_smoke: {dev}, compile cache {cache_dir}; smoke output, "
          f"not benchmark metrics", flush=True)
    qwen = get_config("qwen2.5-3b")
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        phase_sharded(dataclasses.replace(qwen, num_layers=8), split=4)
    print(f"chip_smoke: done at {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
