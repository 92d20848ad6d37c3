"""Decode hot-path microbenchmark: tokens/s, roofline fractions, build walls.

Sweeps {family x seq_len x batch} over the stateful serving decode path
(`StatefulStageRunner`'s whole-stack decode executable) and reports, per
cell and per path variant:

* ``tokens_per_s``        — steady-state decode throughput per device;
* ``cold_build_ms``       — fresh-trace AOT compile wall of the range
  executable (the "new container" cost every pool build pays);
* ``warm_build_ms``       — cached-executable lookup wall;
* ``roofline``            — achieved bytes/s and flops/s of the compiled
  step vs the device roofline (`repro.distributed.roofline`); decode is
  memory-bound, so ``bw_frac`` is the distance from the hardware floor.

Variants:

* ``ref``   — ``decode_impl="reference"``, unrolled Python-loop ranges:
  the pre-kernel serving path, kept as the A/B anchor;
* ``auto``  — ``decode_impl="auto"``, rolled ``lax.scan`` ranges: what
  serving actually runs (kernel routing on TPU, reference on CPU);
* ``kernel`` — ``decode_impl="kernel"``, rolled: the pinned Pallas path.
  On CPU the kernels execute in interpret mode (orders slower — a
  correctness artifact, not a perf number), so this variant only runs
  when the backend is TPU or ``--pin-kernel`` is passed.

Derived per cell: ``impl_speedup_x`` (auto vs ref tokens/s) and
``cold_build_reduction_x`` (ref vs auto cold compile wall — the rolled
lax.scan claim).  Written to ``BENCH_decode.json``; the committed
``BENCH_decode_baseline.json`` guards the trajectory via
``check_regression.py`` and the tier-2 gate.

    PYTHONPATH=src python benchmarks/decode_micro.py [--smoke]

``--smoke`` (the tier-2 CI mode) is FATAL on two conditions:

* the serving path must not lose throughput to the reference path:
  ``auto tokens/s >= ref tokens/s * (1 - DECODE_TOL)`` per cell
  (``DECODE_TOL`` defaults to 0.35 — shared CI hosts jitter);
* the rolled ranges must not regress cold compile wall vs the committed
  baseline: ``auto cold_build_ms <= baseline * BENCH_TOL`` per cell
  (``BENCH_TOL`` defaults to 4.0, the cross-host factor tier-2 uses).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.stateful import DecodeSession, StatefulStageRunner
from repro.distributed.roofline import executable_cost, kernel_roofline
from repro.models import transformer as T

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# family -> (arch, layers): enough layers that the rolled-vs-unrolled
# compile-wall difference is signal, few enough that CPU CI stays fast
FAMILIES = {
    "dense": ("qwen2.5-3b", 8),
    "moe": ("qwen2-moe-a2.7b", 6),
    "ssm": ("falcon-mamba-7b", 8),
    "hybrid": ("zamba2-7b", 6),
}


def _variant(cfg, params, sess, U, x, pos_val, *, decode_impl, rolled,
             seq, steps, build_reps):
    """Measure one path variant: build walls + steady-state decode."""
    r = StatefulStageRunner(cfg, params, max_seq=seq,
                            decode_impl=decode_impl, rolled=rolled)
    cache = sess.subset(0, U)
    pos = jnp.int32(pos_val)
    avals = (jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          x), cache,
             jax.ShapeDtypeStruct((), jnp.int32))

    colds = []
    dec = None
    for _ in range(build_reps):
        t0 = time.perf_counter()
        dec = r.executable("decode", 0, U, params, *avals, fresh=True)
        colds.append(time.perf_counter() - t0)
    r.executable("decode", 0, U, params, *avals)       # populate AOT cache
    t0 = time.perf_counter()
    r.executable("decode", 0, U, params, *avals)       # cache hit
    warm = time.perf_counter() - t0

    out = dec(params, x, cache, pos)                   # first-exec spike
    jax.block_until_ready(out[0])
    t0 = time.perf_counter()
    for _ in range(steps):
        out = dec(params, x, cache, pos)
    jax.block_until_ready(out[0])
    wall = time.perf_counter() - t0

    B = r.hidden(x).shape[0]
    tokens_per_s = B * steps / wall / jax.device_count()
    roof = kernel_roofline(f"decode_{decode_impl}", wall_s=wall / steps,
                           cost=executable_cost(dec))
    return {
        "tokens_per_s": round(tokens_per_s, 2),
        "cold_build_ms": round(float(np.median(colds)) * 1e3, 1),
        "warm_build_ms": round(warm * 1e3, 3),
        "step_ms": round(wall / steps * 1e3, 3),
        "roofline": {
            "achieved_bytes_per_s": round(roof.achieved_bytes_per_s, 1),
            "achieved_flops_per_s": round(roof.achieved_flops_per_s, 1),
            "bw_frac": roof.bw_frac,
            "flops_frac": roof.flops_frac,
            "bound": roof.bound,
        },
    }


def bench_cell(family, *, seq, batch, steps, build_reps, pin_kernel):
    arch, num_layers = FAMILIES[family]
    cfg = replace(get_config(arch).reduced(), num_layers=num_layers)
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    prompt = max(4, seq // 2)
    toks = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt), 0,
                              cfg.vocab_size)
    # one session supplies the (runner-agnostic) state dict, token
    # embedding and position every variant decodes against
    r0 = StatefulStageRunner(cfg, params, max_seq=seq,
                             decode_impl="reference")
    sess = DecodeSession(r0)
    sess.prefill(toks)
    U = len(r0.units)
    x = r0.stream(params["embed"][jnp.asarray(sess.next_token(), jnp.int32)])

    cell = {
        "ref": _variant(cfg, params, sess, U, x, sess.pos,
                        decode_impl="reference", rolled=False, seq=seq,
                        steps=steps, build_reps=build_reps),
        "auto": _variant(cfg, params, sess, U, x, sess.pos,
                         decode_impl="auto", rolled=True, seq=seq,
                         steps=steps, build_reps=build_reps),
    }
    # nk: benchmark-side backend probe (never traced)
    if pin_kernel or jax.default_backend() == "tpu":
        cell["kernel"] = _variant(cfg, params, sess, U, x, sess.pos,
                                  decode_impl="kernel", rolled=True,
                                  seq=seq, steps=steps,
                                  build_reps=build_reps)
    cell["impl_speedup_x"] = round(
        cell["auto"]["tokens_per_s"] / max(cell["ref"]["tokens_per_s"],
                                           1e-9), 3)
    cell["cold_build_reduction_x"] = round(
        cell["ref"]["cold_build_ms"] / max(cell["auto"]["cold_build_ms"],
                                           1e-6), 3)
    return cell


def _geomean(xs):
    xs = [x for x in xs if x > 0]
    return math.exp(sum(math.log(x) for x in xs) / len(xs)) if xs else 0.0


def run(cells_spec, *, steps, build_reps, pin_kernel):
    cells = {}
    for family, seq, batch in cells_spec:
        name = f"{family}_s{seq}_b{batch}"
        print(f"# decode_micro: {name} ...", flush=True)
        cells[name] = bench_cell(family, seq=seq, batch=batch, steps=steps,
                                 build_reps=build_reps,
                                 pin_kernel=pin_kernel)
    summary = {
        "impl_speedup_x": round(_geomean(
            [c["impl_speedup_x"] for c in cells.values()]), 3),
        "cold_build_reduction_x": round(_geomean(
            [c["cold_build_reduction_x"] for c in cells.values()]), 3),
    }
    return cells, summary


def _gate(cells, baseline_path, tol_tokens, tol_build):
    """The --smoke fatal conditions; returns a list of failure rows."""
    fails = []
    for name, cell in cells.items():
        if cell["impl_speedup_x"] < 1.0 - tol_tokens:
            fails.append(
                f"{name}: serving path lost throughput — auto "
                f"{cell['auto']['tokens_per_s']} vs ref "
                f"{cell['ref']['tokens_per_s']} tokens/s "
                f"(speedup {cell['impl_speedup_x']} < {1 - tol_tokens})")
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            base = json.load(f)
        for name, cell in cells.items():
            b = base.get("cells", {}).get(name, {}) \
                    .get("auto", {}).get("cold_build_ms")
            if b and cell["auto"]["cold_build_ms"] > b * tol_build:
                fails.append(
                    f"{name}: cold range-build wall regressed — "
                    f"{cell['auto']['cold_build_ms']} ms vs baseline "
                    f"{b} ms x tol {tol_build}")
    else:
        print(f"# decode_micro: no baseline at {baseline_path}; "
              f"cold-wall gate skipped", file=sys.stderr)
    return fails


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI mode with fatal throughput/build gates")
    ap.add_argument("--pin-kernel", action="store_true",
                    help="also measure the pinned Pallas path (interpret "
                         "mode on CPU: slow, correctness-only numbers)")
    ap.add_argument("--out", default=os.path.join(REPO_ROOT,
                                                  "BENCH_decode.json"))
    ap.add_argument("--baseline",
                    default=os.path.join(REPO_ROOT,
                                         "BENCH_decode_baseline.json"))
    args = ap.parse_args()

    if args.smoke:
        cells_spec = [(f, 128, 1) for f in FAMILIES]
        steps, build_reps = 16, 1
    else:
        cells_spec = [(f, s, b) for f in FAMILIES
                      for s in (128, 256) for b in (1, 4)]
        steps, build_reps = 48, 2

    cells, summary = run(cells_spec, steps=steps, build_reps=build_reps,
                         pin_kernel=args.pin_kernel)
    results = {
        "bench": "decode_micro",
        "smoke": bool(args.smoke),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "cells": cells,
        "summary": summary,
    }
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    print(json.dumps(results, indent=2))
    print(f"# wrote {args.out}")

    if args.smoke:
        tol_tokens = float(os.environ.get("DECODE_TOL", "0.35"))
        tol_build = float(os.environ.get("BENCH_TOL", "4.0"))
        fails = _gate(cells, args.baseline, tol_tokens, tol_build)
        for row in fails:
            print(f"# DECODE GATE FAIL {row}", file=sys.stderr)
        if fails:
            return 1
        print(f"# decode_micro: gates OK (tokens tol {tol_tokens}, "
              f"build tol {tol_build}x)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
