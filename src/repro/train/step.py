"""Train / serve step builders with full sharding annotations.

``make_train_step`` returns a jit-able function
    (params, opt_state, batch) -> (params, opt_state, metrics)
with global-norm clipping and the chosen optimizer.

``make_serve_steps`` returns (prefill_fn, decode_fn) for batched serving.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax

from .optimizer import OptimizerConfig, clip_by_norm, make_optimizer

__all__ = ["TrainConfig", "make_train_step", "make_loss_fn",
           "make_serve_steps"]


@dataclass(frozen=True)
class TrainConfig:
    optimizer: OptimizerConfig = OptimizerConfig()


def make_loss_fn(model):
    def loss_fn(params, batch):
        loss, aux = model.loss(params, batch)
        return loss, aux

    return loss_fn


def make_train_step(model, train_cfg: TrainConfig) -> Callable:
    opt = make_optimizer(train_cfg.optimizer)
    loss_fn = make_loss_fn(model)

    def train_step(params, opt_state, batch):
        (loss, _aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
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
