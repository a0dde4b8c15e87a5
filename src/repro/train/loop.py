"""train_step / serve_step factories: the units the launcher pjits.

``make_train_step`` closes over the model API and optimizer config and
returns a pure ``(state, batch) → (state, metrics)`` function — exactly
what gets ``jax.jit``-ed with in/out shardings by the launcher.
Microbatch gradient accumulation (``grad_accum > 1``)
runs as a ``lax.scan`` over microbatches with an fp32 grad accumulator.

Optional gradient compression (int8 + error feedback) hooks in between
grad computation and the optimizer — see :mod:`repro.dist.compress`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.registry import ModelApi
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update

__all__ = ["TrainState", "make_train_state", "make_train_step", "make_serve_step"]

PyTree = Any


def make_train_state(
    api: ModelApi, key, opt_cfg: Optional[AdamWConfig] = None
) -> Dict[str, Any]:
    params, _ = api.init(key)
    return {
        "params": params,
        "opt": adamw_init(params, opt_cfg),
        "step": jnp.zeros((), jnp.int32),
    }


def make_train_step(
    api: ModelApi,
    opt_cfg: AdamWConfig,
    grad_accum: int = 1,
    compressor: Optional[Any] = None,
):
    """Returns ``train_step(state, batch) -> (state, metrics)``."""

    def loss_fn(params, batch):
        loss, metrics = api.loss(params, batch)
        return loss, metrics

    def compute_grads(params, batch):
        if grad_accum == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
            return loss, metrics, grads

        # split the global batch into microbatches along axis 0
        def slice_mb(x, i):
            mb = x.shape[0] // grad_accum
            return lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)

        def body(carry, i):
            acc, loss_acc = carry
            mb = jax.tree_util.tree_map(lambda x: slice_mb(x, i), batch)
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb
            )
            acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), acc, grads
            )
            return (acc, loss_acc + loss), metrics

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (grads, loss_sum), metrics = lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32)), jnp.arange(grad_accum),
        )
        grads = jax.tree_util.tree_map(lambda g: g / grad_accum, grads)
        metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)
        return loss_sum / grad_accum, metrics, grads

    def train_step(state, batch):
        loss, metrics, grads = compute_grads(state["params"], batch)
        if compressor is not None:
            grads, state = compressor.apply(grads, state)
        params, opt, info = adamw_update(
            opt_cfg, grads, state["opt"], state["params"]
        )
        new_state = dict(state)
        new_state.update(params=params, opt=opt, step=state["step"] + 1)
        out = {"loss": loss, **metrics, **info}
        return new_state, out

    return train_step


def make_serve_step(api: ModelApi):
    """Returns ``serve_step(params, token, pos, cache) -> (logits, cache)``."""

    def serve_step(params, token, pos, cache):
        return api.decode_step(params, token, pos, cache)

    return serve_step
