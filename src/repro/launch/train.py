"""Production training CLI: ``python -m repro.launch.train --arch <id>``.

Selects an assigned architecture config, optionally reduced for local
hardware, and runs the SerPyTor durable trainer (journal + checkpoints +
heartbeat + elastic mesh). On a real TPU pod this is the per-host entry
point; in this container it runs the reduced config on CPU.
"""
from __future__ import annotations

import argparse

from repro.configs import SHAPES, get_config, list_archs, smoke_variant
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import TrainConfig, Trainer


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(list_archs()))
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--run-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="use the reduced same-family config (CPU container)")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="use the full published config (real hardware)")
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--journal-sync", default="batch",
                    choices=["always", "batch", "never"])
    args = ap.parse_args()

    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    if args.reduced:
        cfg = smoke_variant(cfg)
        batch = args.batch or 2
        seq = args.seq or 64
    else:
        batch = args.batch or shape.global_batch
        seq = args.seq or shape.seq_len

    run_dir = args.run_dir or f"runs/{cfg.name}"
    print(f"training {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"{args.steps} steps, batch {batch}×{seq} → {run_dir}")
    tc = TrainConfig(run_dir=run_dir, num_steps=args.steps,
                     checkpoint_every=args.checkpoint_every,
                     global_batch=batch, seq_len=seq,
                     journal_sync=args.journal_sync,
                     opt=AdamWConfig(lr=3e-4, warmup_steps=10,
                                     total_steps=args.steps))
    out = Trainer(cfg, tc).train()
    print(f"done: {out['steps']} steps, {out['steps_per_s']:.2f} steps/s, "
          f"final loss {out['final_loss']}")


if __name__ == "__main__":
    main()
