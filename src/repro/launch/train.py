"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --reduced --steps 50 --batch 8 --seq 128

On real hardware: drop --reduced, point --mesh at the production mesh
(the same sharding rules the dry-run validates are applied), and raise
--batch/--seq to the target shape. Checkpoints resume automatically.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt
from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.data.pipeline import DataConfig, make_batch
from repro.launch.cache import enable_compile_cache
from repro.optim import adam
from repro.sharding import rules
from repro.train.steps import init_all, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale variant of the family (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--micro-batch", type=int, default=0,
                    help="paper's b: grad-accumulation microbatch (0=off)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--remat", default="none",
                    choices=["none", "attn", "full"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tcfg = TrainConfig(
        global_batch=args.batch, micro_batch=args.micro_batch or args.batch,
        seq_len=args.seq, steps=args.steps,
        warmup_steps=max(args.steps // 20, 5), learning_rate=args.lr,
        remat=args.remat, seed=args.seed)

    params, opt = init_all(cfg, args.seed)
    start_step = 0
    if args.ckpt and os.path.exists(args.ckpt):
        state = ckpt.restore(args.ckpt, {"params": params, "opt": opt})
        params, opt = state["params"], state["opt"]
        start_step = int(opt.step)
        print(f"[resume] {args.ckpt} @ step {start_step}")

    step_fn = make_train_step(cfg, tcfg)
    dc = DataConfig(batch=args.batch, seq_len=args.seq, seed=args.seed)
    n_params = cfg.param_count()
    print(f"[train] {cfg.name}  ~{n_params/1e6:.0f}M params  "
          f"B={args.batch} s={args.seq} remat={args.remat} "
          f"devices={len(jax.devices())}")

    t0 = time.time()
    for i in range(start_step, args.steps):
        batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, dc, i).items()}
        params, opt, m = step_fn(params, opt, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            dt = (time.time() - t0) / max(i - start_step + 1, 1)
            print(f"step {i:5d}  loss {float(m['loss']):.4f}  "
                  f"gnorm {float(m['grad_norm']):.2f}  "
                  f"lr {float(m['lr']):.2e}  {dt:.2f}s/step", flush=True)
        if args.ckpt and (i + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt, {"params": params, "opt": opt})
    if args.ckpt:
        ckpt.save(args.ckpt, {"params": params, "opt": opt})
        print(f"[done] checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    main()
