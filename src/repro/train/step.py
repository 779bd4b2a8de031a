"""Train / serve step builders with full sharding annotations.

``make_train_step`` returns a jit-able function
    (params, opt_state, batch) -> (params, opt_state, metrics)
with gradient-accumulation microbatching (lets GSPMD overlap the
reduce-scatter of one microbatch's grads with the next one's backward),
global-norm clipping, and the chosen optimizer.

``make_serve_steps`` returns (prefill_fn, decode_fn) for batched serving.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .optimizer import OptimizerConfig, clip_by_norm, make_optimizer

__all__ = ["TrainConfig", "make_train_step", "make_loss_fn",
           "make_serve_steps"]

PyTree = Any


@dataclass(frozen=True)
class TrainConfig:
    optimizer: OptimizerConfig = OptimizerConfig()
    microbatches: int = 1


def make_loss_fn(model):
    def loss_fn(params, batch):
        loss, aux = model.loss(params, batch)
        return loss, aux

    return loss_fn


def make_train_step(model, train_cfg: TrainConfig) -> Callable:
    opt = make_optimizer(train_cfg.optimizer)
    loss_fn = make_loss_fn(model)
    n_micro = train_cfg.microbatches

    def train_step(params, opt_state, batch):
        if n_micro == 1:
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            def micro(i):
                mb = jax.tree.map(
                    lambda x: jax.lax.dynamic_slice_in_dim(
                        x, i * (x.shape[0] // n_micro),
                        x.shape[0] // n_micro, axis=0), batch)
                return jax.value_and_grad(loss_fn, has_aux=True)(params, mb)

            def body(carry, i):
                acc_g, acc_l = carry
                (l, _aux), g = micro(i)
                acc_g = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), acc_g, g)
                return (acc_g, acc_l + l), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss_sum), _ = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32)),
                jnp.arange(n_micro))
            grads = jax.tree.map(lambda g: g / n_micro, grads)
            loss = loss_sum / n_micro
            aux = {"loss": loss}

        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_norm(grads, train_cfg.optimizer.grad_clip)
            params, opt_state = opt.update(grads, opt_state, params)
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "step": opt_state["step"]}
        return params, opt_state, metrics

    return train_step


def make_serve_steps(model):
    """(prefill, decode_step) for decoder LMs; enc-dec handled by the model's
    own signatures."""

    def prefill(params, tokens, frontend_embeds=None):
        return model.prefill(params, tokens, frontend_embeds)

    def decode(params, cache, token, pos):
        return model.decode_step(params, cache, token, pos)

    return prefill, decode
