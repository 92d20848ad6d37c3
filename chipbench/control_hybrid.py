"""Readings that set the hybrid cell's limits: the program's and the
control's, as ``control.py`` takes them for the other cells.

    python3 chipbench/control_hybrid.py --workload zamba2-7b.chat.switch_b2 --seeds 1,2 --seconds 12

For each seed, in one process: the cell as ``run.py`` runs it and its
own check, then the plain Zamba2 reference in bfloat16 weights and
activations served in the program's place (at each served position of
the checked sessions, the token it puts first) and the cell's check run
on that.  The control's ``correct`` has to come out false.  One JSON
line per seed goes to standard output.  At published widths give each
seed a process of its own: a second seed in the same process holds the
first seed's buffers too (16.1 GB of a v5e's 16 GiB).
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import run as R  # noqa: E402  (sets up paths and cache)


def control_in_place(drv, cycles) -> dict:
    program = dict(drv.notes)
    drv.control = True
    checks = drv.check(cycles)
    return {"control_correct": all(v <= lim for _, v, lim in checks),
            "control_checks": {n: {"value": v, "limit": lim}
                               for n, v, lim in checks},
            "program_notes": program, "control_notes": dict(drv.notes)}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        a = argparse.Namespace(workload=args.workload, seed=seed,
                               seconds=args.seconds, trace=0)
        out = R.run_cell(a, after=control_in_place)
        print(json.dumps({"seed": seed, "correct": out["result"]["correct"],
                          "checks": out["result"]["checks"],
                          "metrics": out["result"]["metrics"],
                          "device": out["result"]["device"],
                          **out["extra"]}, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
