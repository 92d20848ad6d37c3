"""Switch-path microbenchmarks: the perf trajectory tracked every PR.

Three numbers, written to ``BENCH_switch.json`` at the repo root:

* ``build``     — first-build wall time of an edge-cloud pipeline with the
  AOT parallel-stage path vs. the serial trace+execute baseline (the
  pre-AOT ``build`` recipe: jit each stage, run the sample through it,
  block on the result — measured here against fresh closures so neither
  path can hit a cache);
* ``switch``    — serving-thread blocked time per switch for ``switch_a``
  and ``switch_pool(k=1)`` in steady state, vs. the synchronous
  equivalent (blocked + background wall);
* ``optimal_split`` — µs per Eq.-1 solve at 8/32/128 units, with the
  per-unit cost showing the O(n) scaling (an O(n²) implementation grows
  ~16x from 8 to 128; O(n) stays flat).

    PYTHONPATH=src python benchmarks/switch_micro.py [--smoke]

``--smoke`` shrinks repetitions for the ci.sh fast path; the JSON schema
is identical so trajectories stay comparable.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.network import NetworkModel
from repro.core.partitioner import optimal_split
from repro.core.pipeline import EdgeCloudPipeline
from repro.core.profiler import ModelProfile, UnitProfile
from repro.core.stages import StageRunner
from repro.core.switching import PipelineManager
from repro.models import transformer as T

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup(arch="qwen2.5-3b", seq=16):
    cfg = get_config(arch).reduced()
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    runner = StageRunner(cfg, params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, seq), 0,
                              cfg.vocab_size)
    return cfg, runner, {"tokens": toks}


def bench_build(reps=2):
    """Pipeline build wall time: AOT path vs the serial trace+execute
    baseline (the pre-AOT ``build`` recipe).

    ``cold`` is the never-seen configuration (compile-bound; the AOT win
    here is dropping the two sample executions and overlapping the two
    stage compilations — the latter needs >=3 cores to materialise).
    ``warm`` is a configuration the runner compiled before, i.e. every
    pool entry after the first: the baseline still executes the sample
    through both (cached) stages, the AOT path returns the shared
    executables without running anything.
    """
    cfg, runner, inputs = _setup(seq=1024)
    split = 1

    def serial_cold():
        # the pre-AOT recipe: fresh jit, execute sample, block, per stage
        t0 = time.perf_counter()
        edge = runner.fresh_stage_fn(0, split + 1)
        mid = edge(runner.params, inputs)
        jax.block_until_ready(mid)
        cloud = runner.fresh_stage_fn(split + 1, runner.num_units)
        out = cloud(runner.params, mid)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    def serial_warm():
        # pre-AOT warm recipe: cached jit, but the sample still executes
        t0 = time.perf_counter()
        edge = runner.stage_fn(0, split + 1)
        mid = edge(runner.params, inputs)
        jax.block_until_ready(mid)
        cloud = runner.stage_fn(split + 1, runner.num_units)
        out = cloud(runner.params, mid)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    def aot_build(cold):
        # shared weights (like the baseline); cold bypasses every cache
        pipe = EdgeCloudPipeline(runner, split, NetworkModel(20.0))
        t0 = time.perf_counter()
        pipe.build(inputs, cold=cold)
        dt = time.perf_counter() - t0
        pipe.close()
        return dt

    serial_cold()                                # one warmup for jax init
    cold_serial = [serial_cold() for _ in range(reps)]
    cold_aot = [aot_build(cold=True) for _ in range(reps)]
    aot_build(cold=False)                        # populate the AOT cache
    serial_warm()                                # populate the jit cache
    warm_serial = [serial_warm() for _ in range(reps)]
    warm_aot = [aot_build(cold=False) for _ in range(reps)]
    cold = {"serial_trace_execute_ms":
            round(float(np.median(cold_serial)) * 1e3, 1),
            "aot_ms": round(float(np.median(cold_aot)) * 1e3, 1)}
    cold["speedup_x"] = round(cold["serial_trace_execute_ms"]
                              / max(cold["aot_ms"], 1e-6), 2)
    warm = {"serial_trace_execute_ms":
            round(float(np.median(warm_serial)) * 1e3, 1),
            "aot_ms": round(float(np.median(warm_aot)) * 1e3, 1)}
    warm["speedup_x"] = round(warm["serial_trace_execute_ms"]
                              / max(warm["aot_ms"], 1e-6), 2)
    return {"cold": cold, "warm": warm}


def bench_switch(cycles=3):
    """Steady-state serving-thread blocked time per switch."""
    cfg, runner, inputs = _setup()
    hi = max(1, min(2, runner.num_units - 2))
    out = {}
    for spec in ("switch_a", "switch_pool(k=1)"):
        mgr = PipelineManager(runner, split=0, net=NetworkModel(20.0),
                              sample_inputs=inputs,
                              standby_split=hi if spec == "switch_a" else None)
        if spec != "switch_a":
            mgr.get_strategy(spec).prepare(mgr.pool,
                                           candidate_splits=(hi, 0))
        reps = []
        for _ in range(cycles):
            for split in (hi, 0):
                reps.append(mgr.repartition(spec, split))
                mgr.serve(inputs)
        mgr.close()           # settle backgrounds, stop this pool's worker
        steady = reps[2:] or reps
        blocked = float(np.mean([r.t_blocked for r in steady]))
        sync_equiv = float(np.mean([r.t_blocked + r.t_background_wall
                                    for r in steady]))
        out[spec] = {
            "blocked_ms": round(blocked * 1e3, 3),
            "sync_equiv_ms": round(sync_equiv * 1e3, 3),
            "blocked_reduction_x": round(sync_equiv / max(blocked, 1e-9), 1),
        }
    return out


def bench_optimal_split(iters=200, sizes=(8, 32, 128)):
    """µs per Eq.-1 solve; near-constant us_per_unit demonstrates O(n)."""
    rng = np.random.default_rng(0)
    net = NetworkModel(13.0)
    out = {}
    for n in sizes:
        units = [UnitProfile(f"u{i}", float(rng.uniform(1e-4, 1e-2)),
                             float(rng.uniform(1e-4, 1e-2)),
                             int(rng.integers(0, 1_000_000)))
                 for i in range(n)]
        profile = ModelProfile("micro", units)
        optimal_split(profile, net)              # build the prefix cache
        t0 = time.perf_counter()
        for _ in range(iters):
            optimal_split(profile, net)
        us = (time.perf_counter() - t0) / iters * 1e6
        out[f"units_{n}"] = {"us_per_solve": round(us, 1),
                             "us_per_unit": round(us / n, 3)}
    small, big = sizes[0], sizes[-1]
    out["scaling_x_8_to_128"] = round(
        out[f"units_{big}"]["us_per_solve"]
        / out[f"units_{small}"]["us_per_solve"], 1)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI mode: fewer reps, same JSON schema")
    ap.add_argument("--out", default=os.path.join(REPO_ROOT,
                                                  "BENCH_switch.json"))
    args = ap.parse_args()
    reps = 1 if args.smoke else 3
    cycles = 2 if args.smoke else 4
    iters = 50 if args.smoke else 500

    results = {
        "bench": "switch_micro",
        "smoke": bool(args.smoke),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "build": bench_build(reps=reps),
        "switch": bench_switch(cycles=cycles),
        "optimal_split": bench_optimal_split(iters=iters),
    }
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    print(json.dumps(results, indent=2))
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
