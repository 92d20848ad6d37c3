"""Run one benchmark cell once on the chip, and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; everything
else is found by name: its configuration file (``configs``), its traffic
file (``chipbench/traffic/<traffic>.json``), the driver its configuration
names (``chipbench/drivers/<driver>.py``) and one reader per metric
(``chipbench/metrics/<metric>.py``).  Adding a cell, a configuration, a
traffic mix or a metric adds files and entries; nothing here changes.

A run: set-up (weights from the seed, the program's pool, every shape the
cell uses warmed), then whole traffic cycles until ``--seconds`` of wall
time have passed (the window), then the device's peak memory, then the
program's state is freed and what the window served is checked against
the plain reference.  ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` profiles the window and reports its per-layer
metrics.  The last line of standard output is one JSON object; the
numbers compared and their limits end standard error.  Without a TPU,
or with fewer chips than the cell asks for, the run exits 2 and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# the compile cache lives at a fixed path inside the checkout; JAX writes
# its entries there but does not make the directory
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax-cache")
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)


class NoChip(RuntimeError):
    pass


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, name: str) -> tuple:
    """``(cell, config entry, metrics)`` of a workload name, where
    ``metrics`` are the end-to-end and per-layer entries that apply."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]
    return (cell, config, [m for m in bench["end_to_end"] if applies(m)],
            [m for m in bench["per_layer"] if applies(m)])


def device_check(chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise NoChip(f"JAX finds no TPU ({dev}); nothing was run")
    if dev["count"] < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{dev['count']}")
    return dev


def memory_peak() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


class Run:
    """What a metric reader reads."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def run_cell(args, root: Path = ROOT, require_tpu: bool = True,
             after=None) -> dict:
    """One run of a cell; ``after(driver, cycles)``, where given, runs once
    the program's state is freed and its return lands in ``extra``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell, config, e2e, per_layer = find_cell(bench, args.workload)
    cfg = json.loads((root / config["file"]).read_text())
    traffic_path = root / bench["paths"][0] / "traffic" / f"{cell['traffic']}.json"
    from chipbench import traffic as T
    traffic = T.load(traffic_path)
    metrics_dir = root / bench["paths"][0] / "metrics"
    drivers_dir = root / bench["paths"][0] / "drivers"
    readers = {m["name"]: load_module(metrics_dir / f"{m['name']}.py",
                                      "chipbench_metric_" + m["name"])
               for m in (per_layer if args.trace else e2e)}

    import jax
    from repro.launch.compile_cache import CacheEvents, enable_compile_cache
    from chipbench import trace as TR
    from chipbench import work
    dev = device_check(cell["chips"]) if require_tpu else {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": len(jax.devices())}
    enable_compile_cache()
    events = CacheEvents()
    compiles = [0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, *a, **k: compiles.__setitem__(0, compiles[0] + 1)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    spans = TR.Spans(bool(args.trace))
    drv_mod = load_module(drivers_dir / f"{cfg['driver']}.py",
                          "chipbench_driver_" + cfg["driver"])
    drv = drv_mod.Driver(cfg, traffic, args.seed, spans)

    with spans("setup"):
        drv.setup()
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        TR.start(trace_dir)
    cache0, compiles0 = events.counts(), compiles[0]
    setup_s = time.perf_counter() - T_START
    drv.start_window()
    cycles = []
    t0 = time.perf_counter()
    with spans("window"):
        while time.perf_counter() - t0 < args.seconds:
            with spans("cycle"):
                cycles.append(drv.cycle(len(cycles)))
    window_s = time.perf_counter() - t0
    drv.stop_window()
    cache1, compiles1 = events.counts(), compiles[0]
    tr = None
    if args.trace:
        jax.profiler.stop_trace()
        tr = TR.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    peak = memory_peak()
    counts = drv.counts(cycles)
    counts.update(cycles=len(cycles),
                  compile_cache_hits_in_window=cache1[0] - cache0[0],
                  compile_cache_misses_in_window=cache1[1] - cache0[1],
                  backend_compiles_in_window=compiles1 - compiles0)

    run = Run(cfg=cfg, traffic=traffic, cycles=cycles, window_s=window_s,
              setup_s=setup_s, driver=drv, trace=tr,
              peak=work.peaks(dev["kind"]) if require_tpu else None)
    values = {}
    for name, mod in readers.items():
        v = mod.read(run)
        if v is not None:
            values[name] = v
    units = {m["name"]: m["unit"] for m in e2e + per_layer}

    drv.free()
    checks = drv.check(cycles)
    extra = after(drv, cycles) if after is not None else None
    correct = all(v <= lim for _, v, lim in checks)
    device = dict(dev, memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": drv.attempted,
              "failed": drv.failed,
              "metrics": {k: {"value": v, "unit": units[k]}
                          for k, v in values.items()},
              "device": device}
    if tr is not None:
        lo, hi = tr.window()
        device["busy_s"] = TR.busy_ns(tr.ops, lo, hi) * 1e-9 / tr.n_devices
        device["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = {
            "device_ops": TR.top_ops(tr.ops, lo, hi),
            "idle_gaps": TR.idle_by_span(tr.ops, tr.spans, lo, hi)}
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in checks}
    info = {"workload": args.workload, "seed": args.seed, "device": dev,
            "peak_bytes_in_use": peak, "setup_s": setup_s,
            "window_s": window_s, **counts, **drv.notes}
    return {"result": result, "info": info, "checks": checks,
            "extra": extra}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print("chipbench: " + json.dumps(out["info"], default=str), flush=True)
    print(json.dumps(out["result"]), flush=True)
    for name, v, lim in out["checks"]:
        print(f"check {name}: {v!r} (limit {lim!r})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
