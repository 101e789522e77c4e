"""End-to-end LM training driver: any registry architecture, synthetic
corpus, checkpoint/restart, optional ARA gradient compression.

Presets:
  smoke -- reduced config, 200 steps (runs in minutes on CPU; CI default)
  100m  -- qwen1.5-0.5b-family config trimmed to ~100M params, a few hundred
           steps (hours on a single CPU core; sized for a real accelerator)

Run:  PYTHONPATH=src python examples/train_lm.py --arch qwen1.5-0.5b \
          --preset smoke --steps 200
Kill and re-run with the same --ckpt-dir to see auto-resume; SIGTERM
triggers a preemption checkpoint (fault-tolerance demo).
"""

import argparse
import dataclasses

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.optim import AdamWConfig, CompressConfig
from repro.train import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--preset", choices=["smoke", "100m"], default="smoke")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="checkpoints/train_lm")
    ap.add_argument("--compress-rank", type=int, default=0,
                    help="enable ARA low-rank gradient compression")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    enable_compile_cache()

    if args.preset == "smoke":
        cfg = get_config(args.arch, smoke=True)
        batch, seq = args.batch or 8, args.seq or 128
    else:
        cfg = get_config(args.arch)
        # trim to ~100M: 12 layers of the published width
        cfg = dataclasses.replace(cfg, num_layers=12, dtype="float32",
                                  remat=False)
        batch, seq = args.batch or 8, args.seq or 512
        print(f"~{cfg.param_count()/1e6:.0f}M params")

    tcfg = TrainConfig(
        steps=args.steps, batch=batch, seq_len=seq,
        ckpt_dir=args.ckpt_dir, save_every=max(args.steps // 4, 10),
        log_every=10, metrics_path=f"{args.ckpt_dir}/metrics.jsonl",
        optimizer=AdamWConfig(lr=args.lr),
        compress=CompressConfig(rank=args.compress_rank)
        if args.compress_rank else None,
    )
    out = Trainer(cfg, tcfg).run()
    losses = out["losses"]
    if losses:
        print(f"status={out['status']} step={out['step']} "
              f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")


if __name__ == "__main__":
    main()
