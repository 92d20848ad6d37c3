"""Training launcher.

Host-scale run (real execution on this machine):
    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --reduced \
        --steps 200 --batch 8 --seq 64

Production configs are exercised via the dry-run (launch/dryrun.py); this
launcher refuses to materialise a 7B+ model on a laptop on purpose.
"""
from __future__ import annotations

import argparse

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.training import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant (required on CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    elif cfg.param_count() > 1e9:
        raise SystemExit(
            f"{args.arch} has {cfg.param_count()/1e9:.1f}B params; use "
            "--reduced on CPU or launch/dryrun.py for the production mesh")
    hist = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                 lr=args.lr, checkpoint_path=args.checkpoint or None,
                 checkpoint_every=args.checkpoint_every)
    print(f"final loss {hist['loss'][-1]:.4f} "
          f"(first {hist['loss'][0]:.4f}) over {args.steps} steps")


if __name__ == "__main__":
    main()
