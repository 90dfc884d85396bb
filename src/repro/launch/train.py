"""Production train launcher.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --steps 100 --batch 8 --seq 128 --workdir /ckpt/run1

On TPU pods, run once per host (JAX distributed init is picked up from the
TPU environment); on CPU it runs single-process with any smoke-scale config.
Auto-resumes from the newest checkpoint in --workdir; SIGTERM checkpoints
and exits cleanly (preemption-safe).
"""

from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--data", default=None, help="packed int32 token file (memmap)")
    args = ap.parse_args()

    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import count_params
    from repro.train import TrainConfig, Trainer, make_data

    enable_compile_cache()
    cfg = configs.get_smoke_config(args.arch) if args.smoke else configs.get_config(args.arch)
    print(f"[train] {cfg.name}: {count_params(cfg)/1e6:.1f}M params")
    tcfg = TrainConfig(
        lr=args.lr,
        warmup_steps=max(args.steps // 20, 1),
        total_steps=args.steps,
        eval_every=max(args.steps // 20, 1),
        checkpoint_every=max(args.steps // 4, 1),
        microbatch=args.microbatch,
    )
    data = make_data(cfg, args.batch, args.seq, path=args.data)
    result = Trainer(cfg, tcfg, data, workdir=args.workdir).run()
    print(f"[train] done at step {result['step']}; losses: "
          + " ".join(f"{l:.3f}" for l in result.get("losses", [])))


if __name__ == "__main__":
    main()
