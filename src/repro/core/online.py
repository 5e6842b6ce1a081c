"""Online DVI trainer: closes the loop between speculation and learning.

Mirrors the paper's protocol: stream prompts one batch at a time, generate
with tuple logging, then perform small frequent LoRA updates from the
replay buffer (paper: 2000 prompts -> 2000 optimizer steps, each prompt
seen once).  The update is data-parallel-friendly: gradients exist only
for the LoRA adapters (rank x (d + V)), so the all-reduce is a few MB.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import buffer as buffer_mod
from repro.core import losses as losses_mod
from repro.core import scopes
from repro.core import spec as spec_mod
from repro.models.model import Model
from repro.optim import adamw_init, adamw_update


@dataclass
class OnlineTrainerState:
    dvi_params: dict
    opt_state: dict
    buf: dict
    baseline: jax.Array          # EMA of recent rewards (variance reduction)
    step: jax.Array              # optimizer step t (drives the KL->RL schedule)


def init_trainer(model: Model, key, slots: int = 0) -> OnlineTrainerState:
    from repro.core.lora import init_draft_params
    dvi_params = init_draft_params(key, model.cfg)
    return OnlineTrainerState(
        dvi_params=dvi_params,
        opt_state=adamw_init(dvi_params),
        buf=buffer_mod.init_buffer(model.cfg, slots),
        baseline=jnp.float32(0.0),
        step=jnp.int32(0),
    )


def make_update_fn(model: Model, mode: str = "full", lr: float = 1e-3):
    """Jitted: one minibatch LoRA update from the buffer."""
    cfg = model.cfg
    dvi = cfg.dvi

    @jax.jit
    @jax.named_scope(scopes.LEARN_UPDATE)
    def update(params, dvi_params, opt_state, buf, baseline, step, key):
        batch = buffer_mod.sample(buf, key, dvi.batch_size)
        fresh = buffer_mod.fresh_batch(buf, dvi.batch_size) if mode == "full" else None

        def loss_fn(dp):
            return losses_mod.composite_loss(dp, model, params, batch, fresh,
                                             step, baseline, mode)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(dvi_params)
        new_dvi, new_opt, gnorm = adamw_update(dvi_params, grads, opt_state, lr)
        # EMA baseline over observed batch acceptance
        new_baseline = (dvi.baseline_ema * baseline
                        + (1 - dvi.baseline_ema) * metrics["acc_rate"])
        metrics["gnorm"] = gnorm
        # acceptance-EMA baseline around the update (dvi_train_* telemetry)
        metrics["baseline_before"] = baseline
        metrics["baseline_after"] = new_baseline
        metrics["buffer_count"] = buf["count"]
        return new_dvi, new_opt, new_baseline, metrics

    return update


def online_loop(model: Model, params: dict, prompt_stream, state: OnlineTrainerState,
                *, max_new: int = 64, updates_per_batch: int = 1,
                mode: str = "full", lr: float = 1e-3, key=None,
                log_every: int = 0, aux_inputs_fn=None):
    """Run the paper's generate-and-improve loop over a prompt stream.

    prompt_stream: iterable of (B, Tp) int32 arrays (equal Tp per batch).
    Returns (state, history) where history logs per-batch acceptance."""
    key = key if key is not None else jax.random.PRNGKey(0)
    update = make_update_fn(model, mode, lr)
    history = {"acc_rate": [], "block_acc": [], "mat": [], "loss": [], "kl": []}

    @jax.jit
    def gen(params, dvi_params, prompts, buf, aux):
        return spec_mod.speculative_generate(
            model, params, dvi_params, prompts, max_new,
            collect=True, buf=buf, aux_inputs=aux)

    for bi, prompts in enumerate(prompt_stream):
        aux = aux_inputs_fn(prompts) if aux_inputs_fn else None
        res = gen(params, state.dvi_params, prompts, state.buf, aux)
        state.buf = res.buffer
        block_acc = float(res.accepted_drafts) / max(float(res.drafted), 1.0)
        mat = float(res.committed) / max(float(res.blocks), 1.0)

        for _ in range(updates_per_batch):
            key, sub = jax.random.split(key)
            state.dvi_params, state.opt_state, state.baseline, metrics = update(
                params, state.dvi_params, state.opt_state, state.buf,
                state.baseline, state.step, sub)
            state.step = state.step + 1

        history["block_acc"].append(block_acc)
        history["mat"].append(mat)
        history["acc_rate"].append(float(metrics["acc_rate"]))
        history["loss"].append(float(metrics["loss"]))
        history["kl"].append(float(metrics["kl"]))
        if log_every and (bi + 1) % log_every == 0:
            print(f"[online] batch {bi+1}: block_acc={block_acc:.3f} "
                  f"MAT={mat:.2f} loss={history['loss'][-1]:.4f} "
                  f"kl={history['kl'][-1]:.4f} step={int(state.step)}")
    return state, history
