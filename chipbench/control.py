"""Readings that set a cell's limit: the program's and the control's.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 12

Not part of a benchmark run.  For each seed, in one process, it runs the
cell as ``run.py`` does (set-up, a window at the cell's own load, the
program's state freed, the cell's own check), then puts the control in
the program's place and runs the cell's check again:

- for a served LM (float32 at the TPU's default precision) the control
  is the plain reference in bfloat16 weights and activations: at each
  served position of the checked sessions it serves the token that it
  puts first, given the same prompt and served prefix;
- for the CNN (float32 at "highest") the control is the plain reference
  at three bfloat16 passes (``bf16_3x``), served for the same frames.

The control's ``correct`` has to come out false.  One JSON line per seed
goes to standard output, with both sets of checks and the run's notes;
the limit between the two readings is set in the driver, as ``PERF.md``
records.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import run as R  # noqa: E402  (sets up paths and cache)


def _lm_in_place(drv, cycles) -> None:
    import numpy as np

    from chipbench import weights
    from chipbench.reference import qwen2
    picked = drv.sample(cycles)
    params = weights.lm_params(drv.cfg, drv.seed)
    g = qwen2.gaps(drv.cfg, params, [s.tokens for s in picked],
                   [s.prompt_len for s in picked], control=True)
    del params
    for s, first in zip(picked, g["control_first"]):
        s.tokens = np.concatenate([s.tokens[:s.prompt_len],
                                   first.astype(s.tokens.dtype)])


def _cnn_in_place(drv, cycles) -> None:
    import numpy as np

    from chipbench import weights
    from chipbench.reference import vgg
    idx, _, splits = drv.served_arrays
    params = weights.cnn_params(drv.cfg, drv.seed)
    imgs = weights.images(drv.cfg, drv.seed, int(drv.traffic["images"]))
    ctl = np.asarray(vgg.logits(drv.cfg, params, imgs, "bf16_3x"))
    drv.served_arrays = (idx, ctl[idx], splits)


def control_in_place(drv, cycles) -> dict:
    """After the program's own check: the control served in its place,
    and the cell's check run on that."""
    program = dict(drv.notes)
    (_lm_in_place if drv.kind == "sessions" else _cnn_in_place)(drv, cycles)
    checks = drv.check(cycles)
    return {"control_correct": all(v <= lim for _, v, lim in checks),
            "control_checks": {n: {"value": v, "limit": lim}
                               for n, v, lim in checks},
            "program_notes": program, "control_notes": dict(drv.notes)}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        a = argparse.Namespace(workload=args.workload, seed=seed,
                               seconds=args.seconds, trace=0)
        out = R.run_cell(a, after=control_in_place)
        print(json.dumps({"seed": seed, "correct": out["result"]["correct"],
                          "checks": out["result"]["checks"],
                          "metrics": out["result"]["metrics"],
                          **out["extra"]}, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
