import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
against the production meshes with ShapeDtypeStruct stand-ins (no
allocation), then dump memory/cost/collective analysis for the roofline.

MUST be run as its own process (the XLA_FLAGS line above has to execute
before jax initialises devices):

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b \
        --shape train_4k [--multi-pod] [--out experiments/dryrun]

    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import functools
import json
import traceback

import jax
import jax.numpy as jnp

from repro.configs import (ASSIGNED_ARCHS, INPUT_SHAPES, get_config,
                           get_shape, pair_is_runnable)
from repro.core import timing
from repro.distributed.roofline import (Roofline, collective_bytes,
                                        model_flops_estimate)
from repro.distributed.sharding import (cache_shardings, input_shardings,
                                        param_shardings,
                                        should_shard_fsdp_serving)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.models.specs import input_specs
from repro.optim import adamw
from repro.training.steps import (make_prefill_step, make_serve_step,
                                  make_train_step)


def lower_pair(arch: str, shape_name: str, *, multi_pod: bool,
               policy: dict | None = None):
    """Returns (lowered, compiled, meta) for one (arch, shape, mesh)."""
    policy = policy or {}
    cfg = get_config(arch)
    if policy.get("moe_cf") is not None and cfg.moe is not None:
        import dataclasses
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe,
                                         capacity_factor=policy["moe_cf"]))
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    dtype = jnp.bfloat16

    from repro.distributed import policy as pol
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_axes = tuple(a for a in ("pod", "data") if a in sizes)
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    from repro.models.transformer import effective_window as _ew
    attn_mode = policy.get("attn", pol.choose_attn_mode(
        cfg, sizes["model"], kind=shape.kind,
        windowed=_ew(cfg, shape.seq_len) is not None))
    import numpy as _np
    dp_size = int(_np.prod([sizes[a] for a in dp_axes]))
    pol.set_policy(dp=dp, tp="model", attn=attn_mode,
                   tp_size=sizes["model"], dp_size=dp_size,
                   seq_shard_hidden=policy.get("seq_shard_hidden", True))

    params_shape = jax.eval_shape(
        functools.partial(T.init_model, cfg, dtype=dtype),
        jax.random.PRNGKey(0))
    specs, cache_spec = input_specs(cfg, shape, dtype=dtype)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            shard_fsdp = policy.get("train_fsdp", True)
            p_sh = param_shardings(cfg, mesh, params_shape,
                                   shard_fsdp=shard_fsdp)
            step, init_opt = make_train_step(
                cfg, remat=policy.get("remat", True))
            opt_shape = jax.eval_shape(init_opt, params_shape)
            o_sh = param_shardings(cfg, mesh, opt_shape,
                                   shard_fsdp=shard_fsdp)
            in_sh = input_shardings(cfg, mesh, specs, shape)
            fn = jax.jit(step, in_shardings=(p_sh, o_sh, in_sh))
            lowered = fn.lower(params_shape, opt_shape, specs)
        elif shape.kind == "prefill":
            shard_fsdp = policy.get(
                "serve_fsdp", should_shard_fsdp_serving(cfg, mesh))
            p_sh = param_shardings(cfg, mesh, params_shape,
                                   shard_fsdp=shard_fsdp)
            in_sh = input_shardings(cfg, mesh, specs, shape)
            step = make_prefill_step(cfg, shape,
                                     remat=policy.get("remat", True))
            fn = jax.jit(step, in_shardings=(p_sh, in_sh))
            lowered = fn.lower(params_shape, specs)
        else:  # decode
            shard_fsdp = policy.get(
                "serve_fsdp", should_shard_fsdp_serving(cfg, mesh))
            p_sh = param_shardings(cfg, mesh, params_shape,
                                   shard_fsdp=shard_fsdp)
            in_sh = input_shardings(cfg, mesh, specs, shape)
            # default kv layout (post-hillclimb): flash-decode seq-sharding
            # whenever kv heads don't divide tp AND the ring is long enough
            # to slice 128+ slots per shard (EXPERIMENTS.md section Perf A;
            # a window-8192 ring over 256 shards regressed 4x)
            from repro.models.transformer import effective_window
            cl = min(shape.seq_len,
                     effective_window(cfg, shape.seq_len) or shape.seq_len)
            seq_axis_size = sizes["model"] if shape.global_batch >= dp_size \
                else sizes["model"] * dp_size
            kv_default = "seq" if (cfg.num_kv_heads
                                   and cfg.num_kv_heads % sizes["model"]
                                   and cl >= 128 * seq_axis_size) else "heads"
            c_sh = cache_shardings(cfg, mesh, cache_spec, shape,
                                   kv_layout=policy.get("kv_layout", kv_default))
            step = make_serve_step(cfg, shape)
            # donate the cache: aliases the input/output KV buffers so the
            # per-step cache update is in place (no full-cache copy)
            fn = jax.jit(step, in_shardings=(p_sh, in_sh["token"], c_sh),
                         donate_argnums=(2,))
            lowered = fn.lower(params_shape, specs["token"], cache_spec)
        compiled = lowered.compile()
    pol.clear_policy()
    return lowered, compiled, {"chips": chips, "cfg": cfg, "shape": shape,
                               "attn_mode": attn_mode}


def analyse(arch, shape_name, lowered, compiled, meta, *, multi_pod):
    """Roofline terms from the compiled artifact.

    flops/bytes/collectives come from the loop-aware HLO analyzer
    (distributed/hlo_analysis.py) because XLA's cost_analysis counts while
    bodies once (verified; see EXPERIMENTS.md methodology).  The raw XLA
    numbers are kept in the record for reference.
    """
    from repro.distributed.hlo_analysis import analyse_hlo_text
    cfg, shape, chips = meta["cfg"], meta["shape"], meta["chips"]
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    h = analyse_hlo_text(hlo)
    mem = None
    try:
        ma = compiled.memory_analysis()
        mem = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                  + ma.temp_size_in_bytes)
    except Exception:
        pass
    rl = Roofline(
        arch=arch, shape=shape_name,
        mesh="2x16x16" if multi_pod else "16x16", chips=chips,
        hlo_flops=h["flops"] * chips, hlo_bytes=h["bytes"] * chips,
        coll_bytes=h["coll_bytes"] * chips,
        coll_breakdown={"by_kind": h["coll_by_kind"],
                        "counts": h["coll_counts"],
                        "xla_cost_raw": {
                            "flops_per_dev": float(cost.get("flops", 0.0)),
                            "bytes_per_dev": float(cost.get("bytes accessed", 0.0))}},
        model_flops=model_flops_estimate(cfg, shape),
        per_device_bytes=mem,
    ).finish()
    return rl


def run_pair(arch, shape_name, *, multi_pod, out_dir, policy=None,
             tag=""):
    with timing.measure() as m:
        lowered, compiled, meta = lower_pair(arch, shape_name,
                                             multi_pod=multi_pod,
                                             policy=policy)
    t_compile = m.wall
    rl = analyse(arch, shape_name, lowered, compiled, meta,
                 multi_pod=multi_pod)
    rec = rl.to_dict()
    rec["compile_s"] = t_compile
    rec["policy"] = policy or {}
    rec["tag"] = tag
    os.makedirs(out_dir, exist_ok=True)
    mesh_tag = "multipod" if multi_pod else "pod"
    suffix = f"-{tag}" if tag else ""
    path = os.path.join(out_dir,
                        f"{arch}--{shape_name}--{mesh_tag}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"OK  {arch:22s} {shape_name:12s} {rec['mesh']:8s} "
          f"compile {t_compile:6.1f}s  "
          f"Tc {rl.t_compute * 1e3:8.2f}ms Tm {rl.t_memory * 1e3:8.2f}ms "
          f"Tx {rl.t_collective * 1e3:8.2f}ms  [{rl.bottleneck}] "
          f"useful {rl.useful_flops_frac:.2f} "
          f"mem/dev {(rl.per_device_bytes or 0) / 2**30:.2f}GiB",
          flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--policy-json", default="",
                    help='e.g. {"kv_layout": "seq"} — hillclimb variants')
    ap.add_argument("--tag", default="", help="suffix for variant records")
    args = ap.parse_args()
    enable_compile_cache()
    policy = json.loads(args.policy_json) if args.policy_json else None

    pairs = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in INPUT_SHAPES:
                ok, note = pair_is_runnable(a, s)
                if ok:
                    pairs.append((a, s))
                else:
                    print(f"SKIP {a:22s} {s:12s} {note}", flush=True)
    else:
        pairs = [(args.arch, args.shape)]

    failures = []
    for a, s in pairs:
        mesh_tag = "multipod" if args.multi_pod else "pod"
        path = os.path.join(args.out, f"{a}--{s}--{mesh_tag}.json")
        if args.skip_existing and os.path.exists(path):
            print(f"CACHED {a} {s} {mesh_tag}", flush=True)
            continue
        try:
            run_pair(a, s, multi_pod=args.multi_pod, out_dir=args.out,
                     policy=policy, tag=args.tag)
        except Exception as e:
            failures.append((a, s, repr(e)))
            print(f"FAIL {a} {s}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: {failures}")


if __name__ == "__main__":
    main()
