"""Training launcher.

CPU-container scale:   PYTHONPATH=src python -m repro.launch.train \
                          --arch gemma-2b --reduced --steps 100 --batch 8 --seq 128
Production scale: the same entry point with --mesh 16x16 (or 2x16x16 through
the dry-run path) builds the pjit train step with the full sharding rules.
"""
from __future__ import annotations

import argparse

from repro.configs.base import SHAPES, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.runtime import train_loop
from repro.runtime.fault_tolerance import FailureInjector


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--mesh", default=None, help="e.g. 1x1 / 4x2 (data x model)")
    ap.add_argument("--inject-crash-at", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    shape = SHAPES[args.shape]
    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))

    injector = (
        FailureInjector({args.inject_crash_at: "crash"})
        if args.inject_crash_at
        else None
    )
    try:
        state, losses, monitor = train_loop.run_training(
            cfg, shape, mesh,
            num_steps=args.steps,
            seed=args.seed,
            ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every,
            batch_override=args.batch,
            seq_override=args.seq,
            microbatches=args.microbatches,
            grad_compression=args.grad_compression,
            failure_injector=injector,
        )
    except RuntimeError as e:
        print(f"[fault] {e} — restart this command to resume from checkpoint")
        raise SystemExit(42)
    print(
        f"done: {len(losses)} steps, loss {losses[0]:.4f} -> {losses[-1]:.4f},"
        f" straggle events {monitor.events}"
    )


if __name__ == "__main__":
    main()
