"""Training launcher.

Local run (reduced config, real optimization on this host):
    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --reduced --steps 50 --batch 4 --seq 64

Production posture: the same Trainer drives the pjit train_step built by
launch/steps.py on the mesh from launch/mesh.py; on a real multi-host TPU
deployment each host runs this entry point under `jax.distributed`.
"""
from __future__ import annotations

import argparse
import json

from repro.configs import get_config, reduced
from repro.data.pipeline import DataConfig
from repro.training.loop import TrainConfig, Trainer
from repro.training.optim import OptConfig
from repro.launch.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="simulated failure (restart resumes)")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    data_cfg = DataConfig(batch=args.batch, seq_len=args.seq,
                          vocab_size=cfg.vocab_size)
    tc = TrainConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                     ckpt_dir=args.ckpt_dir, fail_at_step=args.fail_at)
    oc = OptConfig(lr=args.lr, total_steps=args.steps, warmup_steps=5)
    trainer = Trainer(cfg, data_cfg, tc, oc)
    out = trainer.run()
    first, last = out["history"][0]["loss"], out["history"][-1]["loss"]
    print(json.dumps({
        "arch": cfg.name, "steps": out["final_step"],
        "loss_first": round(first, 4), "loss_last": round(last, 4),
        "wall_s": round(out["wall_s"], 1),
        "stragglers": out["stragglers"],
    }))


if __name__ == "__main__":
    main()
