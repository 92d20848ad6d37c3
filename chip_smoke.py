"""Smoke run of the serving system on a TPU: its main paths, at published
widths, through the calls a user makes.

    python chip_smoke.py              # one chip: phases cnn and lm
    python chip_smoke.py --chips 4    # four chips: the sharded cloud stage only

Phase ``cnn`` is the paper's path: VGG-19 at 224x224, batch 1, served by
``CnnStageRunner`` -> ``PipelineManager`` -> ``NeukonfigController`` over
the 20 -> 5 -> 20 Mbps trace -> ``ServingEngine``, once per strategy.
Phase ``lm`` serves qwen2.5-3b (all 36 layers, float32) from a 4-slot
``SessionManager`` pool, admits sessions mid-stream, repartitions
18 -> 12 layers with ``switch_b2`` while decoding, and checks every
session's logits against a plain float32 forward pass.  ``--chips 4``
puts the cloud stage of an 8-layer qwen2.5-3b on a (4,) mesh, compares
it with a single-device cloud, then moves it to a (2,) mesh.

Every number printed is smoke output from one run, not a benchmark
metric.  The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``; a failed phase or check, or a run
where JAX finds no TPU, exits non-zero without it.
"""
import argparse
import dataclasses
import functools
import json
import sys
import tempfile
import time
import warnings
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint import save_pytree  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import (BackgroundBuildFailed, BandwidthTrace,  # noqa: E402
                        NeukonfigController, NetworkModel, PipelineManager,
                        optimal_split, profile_cnn)
from repro.core.pool import SwitchAbortedWarning  # noqa: E402
from repro.core.stages import CnnStageRunner  # noqa: E402
from repro.core.stateful import HandoffIntegrityWarning  # noqa: E402
from repro.core.strategies import StandbySplitMismatch  # noqa: E402
from repro.launch.compile_cache import (CacheEvents,  # noqa: E402
                                        enable_compile_cache)
from repro.models import transformer as T  # noqa: E402
from repro.serving import (ServingEngine, VirtualClock,  # noqa: E402
                           make_session_manager, request_stream)

CNN_STRATEGIES = ("pause_resume", "switch_a", "switch_b1", "switch_b2",
                  "switch_pool(k=1)")
# Served VGG-19 logits vs the unsplit forward, as a fraction of the
# largest reference logit: both run the same ops at the TPU's default
# float32 precision (bf16 matmul passes), in programs XLA may fuse and
# tile differently, so they agree to that rounding, not bit for bit.
CNN_TOL = 1e-2
# Served qwen2.5-3b logits vs a float32 forward at "highest" matmul
# precision, as a fraction of the largest reference logit.  The served
# path runs its float32 matmuls at the TPU's default precision, which
# rounds their inputs to bf16 (unit roundoff 2^-9, about 2e-3); some 250
# of them in sequence (36 layers x 7) drift by a few 1e-2, and the
# largest of 151936 logit errors sits above that.  A wrong layer,
# position or cache entry is off by order 1.
LM_TOL = 0.1
# Sharded vs single-device cloud, both compiled at "highest" matmul
# precision: float32 throughout, and only the all-reduce changes the
# order of the sums.  (At the default precision the two programs round
# to bf16 at different points and differ by up to 3e-3 of max|logit|,
# which would hide a sharding error of that size.)
SHARD_TOL = 1e-4
SEED = 0          # random weights, prompts and images all come from it


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
# phase cnn: the paper's path
# ---------------------------------------------------------------------------

def phase_cnn(cfg, events: CacheEvents) -> None:
    base = CnnStageRunner(cfg, key=jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(SEED)
    sample = {"image": jnp.asarray(rng.standard_normal(
        (1, cfg.input_hw, cfg.input_hw, cfg.input_ch), dtype=np.float32))}
    ref = base.stage_fn(0, base.num_units)(base.params, sample)["logits"]
    profile = profile_cnn(cfg, base.params, base.units, base.shapes, reps=1)
    trace = BandwidthTrace(steps=[(0.0, 20.0), (8.0, 5.0), (16.0, 20.0)])
    duration, fps = 24.0, 2.0
    s0 = optimal_split(profile, trace.at(0.0)).split
    s1 = optimal_split(profile, trace.at(8.0)).split
    moves = s1 != s0
    if not moves:
        # the measured profile keeps one optimum on this trace: script
        # one repartition inside the 5 Mbps window; the controller then
        # moves the split back to its optimum at 20 Mbps
        s1 = s0 - 2 if s0 >= 2 else s0 + 2
    print(f"[cnn] {cfg.name} {cfg.input_hw}x{cfg.input_hw} batch 1; "
          f"profiled optimum {s0} at 20 Mbps, "
          f"{'%d' % s1 if moves else 'unchanged'} at 5 Mbps; "
          f"repartition {s0}->{s1}"
          f"{'' if moves else ' scripted at t=12 s'}", flush=True)
    with tempfile.TemporaryDirectory() as tmp:
        ckpt = str(Path(tmp) / "vgg19.npz")
        save_pytree(base.params, ckpt)
        for spec in CNN_STRATEGIES:
            runner = CnnStageRunner(cfg, params=base.params)
            mgr = PipelineManager(runner, split=s0, net=trace.at(0.0),
                                  sample_inputs=sample, warm_standbys=True,
                                  checkpoint_path=ckpt)
            ctl = NeukonfigController(mgr, profile, trace, strategy=spec,
                                      candidate_splits=[s0, s1])
            try:
                eng = ServingEngine(mgr, clock=VirtualClock(), controller=ctl)
                if not moves:
                    eng.schedule_switch(12.0, spec, s1)
                hits0, miss0 = events.counts()
                tl = eng.run(request_stream(sample, fps=fps,
                                            duration=duration),
                             duration=duration)
                mgr.drain()
                hits, miss = events.counts()
                logits, _ = mgr.serve(sample)
                err = rel_err(logits, ref)
                check(tl.windows, f"{spec}: no repartition happened")
                check(not any(w.aborted for w in tl.windows),
                      f"{spec}: a switch was aborted")
                check(not tl.degraded, f"{spec}: degraded-mode window")
                check(err <= CNN_TOL, f"{spec}: logits off by {err:.3g} "
                      f"of max|logit| (limit {CNN_TOL})")
                for w, rep in zip(tl.windows, eng.reports):
                    print(f"[cnn] {spec:16s} {w.old_split}->{w.new_split} "
                          f"switch window {w.duration * 1e3:.3f} ms, "
                          f"build {rep.t_build * 1e3:.3f} ms", flush=True)
                print(f"[cnn] {spec:16s} persistent compile cache during "
                      f"serving: {hits - hits0} hit(s), {miss - miss0} "
                      f"miss(es); logits vs unsplit: {err:.3g} of max|logit|",
                      flush=True)
            finally:
                ctl.close()


# ---------------------------------------------------------------------------
# phase lm: the language-model serving path
# ---------------------------------------------------------------------------

def reference_logits(cfg, params, tokens):
    """Next-token logits after each row of ``tokens`` (a list of 1-D
    arrays) from a plain float32 full-sequence forward pass at "highest"
    matmul precision.  One jitted layer at a time: a whole-stack program
    would convert every layer's weights at once."""
    n, S = len(tokens), max(len(t) for t in tokens)
    ids = np.zeros((n, S), np.int32)
    for i, t in enumerate(tokens):
        ids[i, :len(t)] = t          # causal: the pad never reaches t
    rope = T._rope_for(cfg, S)

    @jax.jit
    def layer(x, lp):
        return T.attn_block_full(cfg, lp, x, rope, impl="naive")[0]

    @jax.jit
    def head(x, params):
        return T._apply_norm(cfg, params["final_norm"], x) \
            @ T.lm_head_weights(cfg, params)

    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(ids)]
        for i in range(cfg.num_layers):
            x = layer(x, jax.tree.map(lambda a: a[i], params["layers"]))
        last = x[jnp.arange(n), jnp.asarray([len(t) - 1 for t in tokens])]
        return np.asarray(head(last, params))


def phase_lm(cfg, events: CacheEvents, *, split: int,
             new_split: int) -> None:
    params = jax.jit(functools.partial(T.init_model, cfg))(
        jax.random.PRNGKey(SEED))
    mgr, sm = make_session_manager(cfg, params, split=split,
                                   net=NetworkModel(20.0), num_slots=4,
                                   max_seq=256, seed=SEED)
    try:
        runner = sm.runner
        rng = np.random.default_rng(SEED)
        prompts = [rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(8, 65))).astype(np.int32)
                   for _ in range(4)]
        print(f"[lm] {cfg.name}: {cfg.num_layers} layers, d_model "
              f"{cfg.d_model}, vocab {cfg.vocab_size}, float32; 4 slots, "
              f"max_seq 256, prompts {[len(p) for p in prompts]}",
              flush=True)
        for i in (0, 1):
            sm.admit(prompts[i], sid=f"s{i}")
        eng = ServingEngine(mgr, clock=VirtualClock())
        eng.schedule_admit(1.0, prompts[2], sid="s2")
        eng.schedule_admit(1.5, prompts[3], sid="s3")
        eng.schedule_switch(2.5, "switch_b2", new_split)
        hits0, miss0 = events.counts()
        # long enough to keep decoding after a switch that compiles cold
        duration = 16.0
        tl = eng.run(request_stream({}, fps=4.0, duration=duration),
                     duration=duration)
        hits, miss = events.counts()
        check(len(tl.windows) == 1 and not tl.windows[0].aborted,
              "the switch_b2 repartition did not complete")
        check(not tl.degraded, "degraded-mode window")
        w, rep = tl.windows[0], eng.reports[0]
        check(rep.handoff_mode in ("transfer", "recompute"),
              f"no state hand-off ({rep.handoff_mode!r})")
        pipe = mgr.pool.active
        check(pipe.split == new_split, f"serving split {pipe.split}")
        steps = [sum(r.served and r.split == sp for r in tl.records)
                 for sp in (split, new_split)]
        check(min(steps) >= 4, f"decode steps at splits {split}, "
              f"{new_split}: {steps}")
        require_kernels(runner, pipe.edge_fn, pipe.cloud_fn)
        print(f"[lm] switch_b2 {w.old_split}->{w.new_split} while decoding: "
              f"switch window {w.duration * 1e3:.3f} ms, build "
              f"{rep.t_build * 1e3:.3f} ms, hand-off {rep.handoff_mode} "
              f"{rep.handoff_bytes} bytes {rep.t_handoff * 1e3:.3f} ms; "
              f"persistent compile cache during serving: {hits - hits0} "
              f"hit(s), {miss - miss0} miss(es); decode steps served at "
              f"{split}/{new_split}: {steps[0]}/{steps[1]}, p50 step "
              f"latency {tl.p50 * 1e3:.3f} ms (stream clock, edge x4)",
              flush=True)
        sids = sorted(sm.session_ids())
        check(sids == ["s0", "s1", "s2", "s3"], f"live sessions {sids}")
        toks = [sm.tokens_for(s) for s in sids]
        decoded = [len(t) - len(p) for t, p in zip(toks, prompts)]
        check(min(decoded) >= 8, f"decoded tokens per session {decoded}")
        ref = reference_logits(cfg, params, toks)
        errs = [rel_err(sm.logits_for(s), r) for s, r in zip(sids, ref)]
        print(f"[lm] decode_impl {runner.resolved_decode_impl} "
              f"(tpu_custom_call in the edge and cloud executables); "
              f"tokens decoded per session {decoded}; logits vs float32 "
              f"reference {[f'{e:.3g}' for e in errs]} of max|logit| "
              f"(limit {LM_TOL})", flush=True)
        check(max(errs) <= LM_TOL, "served logits off the reference")
        stats = jax.devices()[0].memory_stats() or {}
        print(f"[lm] peak_bytes_in_use "
              f"{gib(stats.get('peak_bytes_in_use', 0))}, bytes_limit "
              f"{gib(stats.get('bytes_limit', 0))}", flush=True)
        print("[lm] not run at this width: pause_resume (reloads a second "
              "weight copy from the checkpoint while the runner's stays "
              "live), switch_a, switch_b1 and switch_pool (own a standby "
              "weight copy): two float32 copies of 3.09 B parameters do "
              "not fit one 16 GiB chip", flush=True)
    finally:
        mgr.close()


# ---------------------------------------------------------------------------
# --chips 4: the sharded cloud stage
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
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded cloud stage on a (4,) mesh")
    args = ap.parse_args()
    cache_dir = enable_compile_cache()
    dev = device_line()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX finds no TPU ({dev}); nothing was run",
              file=sys.stderr)
        return 2
    check(dev["count"] >= args.chips,
          f"--chips {args.chips} on a host with {dev['count']} device(s)")
    for cat in (StandbySplitMismatch, SwitchAbortedWarning,
                BackgroundBuildFailed, HandoffIntegrityWarning):
        warnings.simplefilter("error", cat)
    print(f"chip_smoke: {dev}, compile cache {cache_dir}; smoke output, "
          f"not benchmark metrics", flush=True)
    qwen = get_config("qwen2.5-3b")
    t0 = time.perf_counter()
    if args.chips == 4:
        with jax.default_matmul_precision("highest"):
            phase_sharded(dataclasses.replace(qwen, num_layers=8), split=4)
    else:
        events = CacheEvents()
        phase_cnn(get_config("vgg19"), events)
        print(f"chip_smoke: cnn done at {time.perf_counter() - t0:.1f} s",
              flush=True)
        phase_lm(qwen, events, split=18, new_split=12)
    print(f"chip_smoke: done at {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
