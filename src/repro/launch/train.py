"""Training launcher.

On a TPU pod this builds the production mesh and runs the full config;
on CPU (this container) use --smoke to run the reduced same-family
config end-to-end (the quickstart path), e.g.:

  python -m repro.launch.train --arch gemma2-2b --smoke --steps 25 \
      --ckpt-dir /tmp/ckpt

Demonstrates the full production loop: sharded step, grad accumulation,
async checkpoints, restart-from-latest (rerun the same command after a
kill), straggler detection.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=25)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--fail-at-step", type=int, default=None,
                    help="inject a simulated failure (fault-tolerance demo)")
    ap.add_argument("--production-mesh", action="store_true",
                    help="build the (16,16) mesh (requires 256 devices)")
    args = ap.parse_args()

    from repro.configs import SHAPES, get_config
    from repro.configs.base import ShapeConfig
    from repro.configs.smoke import smoke_config
    from repro.core import tuning
    from repro.launch.compile_cache import place_compile_cache
    from repro.launch.mesh import make_production_mesh
    from repro.train import TrainConfig, Trainer

    # Pick up persisted per-arch tuning caches before the step traces:
    # block_*=None then resolves to autotuned winners, no re-tuning.
    # (No-op if repro.kernels already auto-loaded them at import.)
    tuning.load_caches()
    place_compile_cache()

    if args.smoke:
        cfg = smoke_config(args.arch)
        shape = ShapeConfig("smoke", args.seq_len, args.batch, "train")
    else:
        cfg = get_config(args.arch)
        shape = SHAPES[args.shape]

    mesh = make_production_mesh() if args.production_mesh else None
    tc = TrainConfig(steps=args.steps, peak_lr=args.lr,
                     microbatches=args.microbatches,
                     ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                     fail_at_step=args.fail_at_step)
    trainer = Trainer(cfg, shape, tc, mesh=mesh)
    result = trainer.run()
    hist = result["history"]
    print(json.dumps({
        "arch": args.arch,
        "steps_run": len(hist),
        "first_loss": hist[0]["loss"] if hist else None,
        "last_loss": hist[-1]["loss"] if hist else None,
        "mean_step_s": sum(h["time_s"] for h in hist) / max(len(hist), 1),
        "stragglers": result["stragglers"],
    }, indent=1))


if __name__ == "__main__":
    main()
