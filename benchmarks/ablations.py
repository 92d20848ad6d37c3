"""Ablations over the distribution policy, run as dry-run sweeps (each point
is a fresh 512-device subprocess compile; roofline terms from the JSON).

1. kv cache layout (heads vs seq) on GQA decode — validates the
   flash-decode-sharding default (EXPERIMENTS.md Pair A).
2. MoE capacity factor on qwen2-moe prefill — dropped-token compute vs
   buffer traffic trade-off.
3. PipelinePool memory budget on switch_pool(k=2) — how LRU eviction
   degrades the speculative hit rate as the edge budget shrinks (runs
   in-process, no subprocess).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

OUT = "experiments/ablations"


def _run(arch, shape, policy, tag):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # fake-device compiles by design: never contend for the parent's chip
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--out", OUT, "--tag", tag]
    if policy:
        cmd += ["--policy-json", json.dumps(policy)]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=900)
    mesh = "pod"
    path = os.path.join(OUT, f"{arch}--{shape}--{mesh}-{tag}.json")
    if not os.path.exists(path):
        raise RuntimeError(f"{arch}/{shape}/{tag} failed:\n{r.stdout[-800:]}"
                           f"\n{r.stderr[-800:]}")
    return json.load(open(path))


def run():
    rows = []
    # 1. kv layout on GQA decode
    for arch in ("yi-34b", "qwen2.5-3b"):
        for layout in ("heads", "seq"):
            rec = _run(arch, "decode_32k", {"kv_layout": layout}, f"kv_{layout}")
            rows.append({
                "name": f"kvlayout/{arch}/{layout}",
                "value": round(max(rec["t_compute"], rec["t_memory"],
                                   rec["t_collective"]) * 1e3, 2),
                "t_memory_ms": round(rec["t_memory"] * 1e3, 2),
                "t_collective_ms": round(rec["t_collective"] * 1e3, 2),
            })
            print(f"# {rows[-1]['name']:32s} dominant {rows[-1]['value']:9.2f} ms")
    # 2. MoE capacity factor
    for cf in (1.0, 1.25, 2.0):
        rec = _run("qwen2-moe-a2.7b", "prefill_32k", {"moe_cf": cf},
                   f"cf{cf}")
        rows.append({
            "name": f"capacity_factor/qwen2-moe/{cf}",
            "value": round(max(rec["t_compute"], rec["t_memory"],
                               rec["t_collective"]) * 1e3, 2),
            "t_memory_ms": round(rec["t_memory"] * 1e3, 2),
            "mem_gib": round(rec["per_device_bytes"] / 2 ** 30, 2),
        })
        print(f"# {rows[-1]['name']:32s} dominant {rows[-1]['value']:9.2f} ms "
              f"mem {rows[-1]['mem_gib']} GiB")
    emit(rows, "ablations")
    return rows


def run_pool_budget(arch="qwen2.5-3b", cycles=3):
    """Edge-memory budget vs switch_pool hit rate (paper sec. IV-B analogue:
    the edge cannot host standbys it has no memory for)."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.network import NetworkModel
    from repro.core.stages import StageRunner
    from repro.core.switching import PipelineManager
    from repro.models import transformer as T

    cfg = get_config(arch).reduced()
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0,
                              cfg.vocab_size)
    inputs = {"tokens": toks}
    pbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    rows = []
    for budget_x in (None, 1.5, 0.5):
        runner = StageRunner(cfg, params)
        budget = int(budget_x * pbytes) if budget_x is not None else None
        mgr = PipelineManager(runner, 1, NetworkModel(20.0), inputs,
                              mem_budget_bytes=budget)
        reps = [mgr.repartition("switch_pool(k=2)", s)
                for _ in range(cycles) for s in (2, 1)]
        mgr.close()           # settle trailing speculation before accounting
        mem = mgr.memory_report()
        rows.append({
            "name": f"pool_budget/{arch}/"
                    f"{'unlimited' if budget_x is None else budget_x}x",
            "value": round(float(np.mean([r.downtime
                                          for r in reps[2:]])) * 1e3, 3),
            "hit_rate": round(float(np.mean([r.cache_hit
                                             for r in reps[2:]])), 2),
            "additional_mb": round(mem["additional_bytes"] / 2 ** 20, 2),
        })
        print(f"# {rows[-1]['name']:36s} steady {rows[-1]['value']:9.3f} ms "
              f"hits {rows[-1]['hit_rate']:.2f} "
              f"(+{rows[-1]['additional_mb']} MB)")
    emit(rows, "ablation_pool_budget")
    return rows


def main():
    run_pool_budget()
    run()


if __name__ == "__main__":
    main()
