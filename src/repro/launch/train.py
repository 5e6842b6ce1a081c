"""Training launcher.

Modes:
  pretrain    — full-backbone LM pretraining (substrate; tiny archs on CPU)
  dvi-online  — the paper's protocol: speculative generation with logging +
                online LoRA updates over a prompt stream
  dvi-batch   — teacher-forced DVI drafter updates over token batches
                (the `train_4k` dry-run workload, runnable for tiny archs)

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch vicuna-7b --tiny \\
      --mode dvi-online --prompts 200 --batch 8 --max-new 24
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint, save_lora
from repro.configs import get_config
from repro.core import online as online_mod
from repro.data import SyntheticTasks, TASK_CATEGORIES
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.optim import adamw_init
from repro.training import make_dvi_train_step, pretrain


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="vicuna-7b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--mode", default="dvi-online",
                    choices=["pretrain", "dvi-online", "dvi-batch"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--prompts", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--loss-mode", default="full",
                    choices=["full", "kl", "pg", "ce"])
    ap.add_argument("--pretrain-steps", type=int, default=200,
                    help="backbone warmup before DVI modes")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--dtype", default="float32")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, tiny=args.tiny).replace(dtype=args.dtype)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    tasks = SyntheticTasks(cfg.vocab_size, seed=args.seed)
    t0 = time.time()

    if args.mode == "pretrain" or args.pretrain_steps:
        n = args.steps if args.mode == "pretrain" else args.pretrain_steps
        params, losses = pretrain(
            model, params, tasks.stream(TASK_CATEGORIES, n, args.batch,
                                        args.seq, seed=args.seed + 1),
            lr=2e-3, log_every=max(n // 4, 1))
        print(f"[train] pretrain {n} steps: loss {losses[0]:.3f} -> "
              f"{losses[-1]:.3f} ({time.time()-t0:.1f}s)")
        if args.mode == "pretrain":
            if args.ckpt:
                save_checkpoint(args.ckpt, params)
            return

    state = online_mod.init_trainer(model, jax.random.PRNGKey(args.seed + 7))

    if args.mode == "dvi-online":
        n_batches = max(args.prompts // args.batch, 1)
        stream = tasks.stream(TASK_CATEGORIES, n_batches, args.batch,
                              args.seq // 2, seed=args.seed + 2)
        state, hist = online_mod.online_loop(
            model, params, stream, state, max_new=args.max_new,
            mode=args.loss_mode, lr=args.lr,
            log_every=max(n_batches // 10, 1))
        acc = np.array(hist["block_acc"])
        print(f"[train] dvi-online: block_acc {acc[:5].mean():.3f} -> "
              f"{acc[-5:].mean():.3f}; MAT {np.mean(hist['mat'][-5:]):.2f} "
              f"({time.time()-t0:.1f}s)")
    else:
        step_fn = make_dvi_train_step(model, lr=args.lr, mode=args.loss_mode)
        opt = adamw_init(state.dvi_params)
        baseline = jnp.float32(0.0)
        dvi_params = state.dvi_params
        for i, tokens in enumerate(tasks.stream(
                TASK_CATEGORIES, args.steps, args.batch, args.seq,
                seed=args.seed + 3)):
            dvi_params, opt, baseline, metrics = step_fn(
                params, dvi_params, opt, jnp.asarray(tokens), jnp.int32(i),
                baseline)
            if (i + 1) % max(args.steps // 10, 1) == 0:
                print(f"[train] dvi-batch step {i+1}: "
                      f"acc={float(metrics['acc_rate']):.3f} "
                      f"loss={float(metrics['loss']):.4f}")
        state.dvi_params = dvi_params

    if args.ckpt:
        save_lora(args.ckpt, state.dvi_params, int(state.step),
                  float(state.baseline))
        print(f"[train] saved LoRA checkpoint to {args.ckpt}")


if __name__ == "__main__":
    main()
