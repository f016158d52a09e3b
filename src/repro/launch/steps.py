"""Step builders: train_step / serve_step per architecture."""
from __future__ import annotations

import os
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.model import build_model
from repro.optim import get_optimizer, get_schedule


def make_train_state(cfg: ModelConfig, rng):
    fns = build_model(cfg)
    params = fns.init(rng)
    opt = get_optimizer(cfg.optimizer)
    return {"params": params, "opt": opt.init(params), "step": jnp.zeros((), jnp.int32)}


def make_train_step(
    cfg: ModelConfig,
    peak_lr: float = 3e-4,
    warmup: int = 100,
    total_steps: int = 10_000,
    microbatches: int = 0,
):
    """``microbatches`` > 1 enables gradient accumulation: the batch splits
    along dim 0 and a scan accumulates grads, dividing peak activation
    memory (the remat carry stack) by the microbatch count at the cost of
    smaller per-matmul shapes. Overridable via REPRO_MICROBATCH."""
    fns = build_model(cfg)
    opt = get_optimizer(cfg.optimizer)
    sched = get_schedule(cfg.schedule, peak_lr, warmup, total_steps)
    n_micro = microbatches or int(os.environ.get("REPRO_MICROBATCH", "0") or 0)

    def _split(batch, i):
        def per(v):
            if v.ndim >= 1 and v.shape[0] % n_micro == 0:
                mb = v.shape[0] // n_micro
                return jax.lax.dynamic_slice_in_dim(v, i * mb, mb, axis=0)
            if v.ndim >= 2 and v.shape[1] % n_micro == 0:  # positions3 (3,B,S)
                mb = v.shape[1] // n_micro
                return jax.lax.dynamic_slice_in_dim(v, i * mb, mb, axis=1)
            return v
        return {k: per(v) for k, v in batch.items()}

    def train_step(state: Dict[str, Any], batch: Dict[str, Any]):
        def loss_fn(params, b):
            return fns.loss(params, b)

        if n_micro > 1:
            def micro_step(acc, i):
                (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    state["params"], _split(batch, i)
                )
                acc = jax.tree.map(
                    lambda a, gi: a + gi.astype(a.dtype) / n_micro, acc[0], g
                ), (acc[1][0] + loss / n_micro, acc[1][1])
                return acc, None
            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"]
            )
            aux0 = {"ce": jnp.float32(0.0), "aux": jnp.float32(0.0)}
            (grads, (loss, aux)), _ = jax.lax.scan(
                micro_step, (zero, (jnp.float32(0.0), aux0)),
                jnp.arange(n_micro),
            )
        else:
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state["params"], batch
            )
        lr = sched(state["step"])
        new_params, new_opt = opt.update(grads, state["opt"], state["params"], lr)
        gnorm = jnp.sqrt(
            sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads)
            )
        )
        metrics = {
            "loss": loss.astype(jnp.float32),
            "ce": aux["ce"].astype(jnp.float32),
            "grad_norm": gnorm,
            "lr": lr,
        }
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "step": state["step"] + 1,
        }
        return new_state, metrics

    return train_step


def make_serve_step(cfg: ModelConfig):
    """Decode: one new token against a seq_len-sized cache (per assignment)."""
    fns = build_model(cfg)

    def serve_step(params, cache, batch):
        return fns.decode_step(params, cache, batch)

    return serve_step
