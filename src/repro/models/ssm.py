"""Pure SSM LM (mamba2 family): uniform scan of Mamba2 SSD blocks.

Block = RMSNorm → Mamba2 mixer → residual (no separate MLP, per the
published architecture).  O(1)-state decode is what makes the long_500k
cell runnable for this family.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.dist.logical import constrain
from repro.models.common import (
    REMAT_POLICY,
    chunked_xent,
    compute_dtype,
    embed_apply,
    embed_init,
    rmsnorm,
    rmsnorm_init,
    last_token_logits,
    unembed_logits,
)
from repro.models.mamba2 import (
    mamba_apply,
    mamba_decode,
    mamba_init,
    mamba_state_init,
)
from repro.models.transformer import _stack_inits

__all__ = [
    "init_ssm",
    "ssm_forward",
    "ssm_loss",
    "ssm_prefill",
    "ssm_decode_step",
    "ssm_cache_init",
]


def _layer_init(key, cfg: ModelConfig):
    p, s = {}, {}
    p["ln"], s["ln"] = rmsnorm_init(cfg.d_model)
    p["mamba"], s["mamba"] = mamba_init(key, cfg)
    return p, s


def init_ssm(cfg: ModelConfig, key):
    ks = jax.random.split(key, 2)
    params, specs = {}, {}
    params["embed"], specs["embed"] = embed_init(ks[0], cfg)
    params["blocks"], specs["blocks"] = _stack_inits(
        lambda k: _layer_init(k, cfg), ks[1], cfg.n_layers
    )
    params["final_norm"], specs["final_norm"] = rmsnorm_init(cfg.d_model)
    return params, specs


def ssm_forward(params, cfg: ModelConfig, tokens: jax.Array):
    x = embed_apply(params["embed"], cfg, tokens)

    def body(x, blk):
        x = constrain(x, "batch", "seq_sp", None)
        h = rmsnorm(x, blk["ln"], cfg.norm_eps)
        x = x + mamba_apply(blk["mamba"], cfg, h)
        return x, None

    body = jax.checkpoint(body, policy=REMAT_POLICY)
    x, _ = lax.scan(body, x, params["blocks"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return constrain(x, "batch", "seq", None), jnp.zeros((), jnp.float32)


def ssm_loss(params, cfg: ModelConfig, tokens, loss_mask=None):
    hidden, _ = ssm_forward(params, cfg, tokens)
    mask = None if loss_mask is None else loss_mask[:, 1:]
    xent = chunked_xent(params["embed"], cfg, hidden[:, :-1], tokens[:, 1:], mask)
    return xent, {"xent": xent, "aux": jnp.zeros((), jnp.float32)}


def ssm_cache_init(cfg: ModelConfig, batch: int, max_len: int = 0):
    cdt = compute_dtype(cfg)
    one = mamba_state_init(cfg, batch, cdt)
    cache = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape), one
    )
    spec = {
        "ssm": ("layers", "batch", "ssm_heads", None, None),
        "conv": ("layers", "batch", None, "conv_dim"),
    }
    return cache, spec


def ssm_prefill(params, cfg: ModelConfig, tokens, max_len: Optional[int] = None,
                lengths=None):
    x = embed_apply(params["embed"], cfg, tokens)

    def body(x, blk):
        h = rmsnorm(x, blk["ln"], cfg.norm_eps)
        y, st = mamba_apply(blk["mamba"], cfg, h, return_state=True)
        return x + y, st

    x, cache = lax.scan(body, x, params["blocks"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = last_token_logits(params["embed"], cfg, x, lengths=lengths)
    return logits, cache


def ssm_decode_step(params, cfg: ModelConfig, token, pos, cache):
    x = embed_apply(params["embed"], cfg, token)

    def body(x, xs):
        blk, st = xs
        h = rmsnorm(x, blk["ln"], cfg.norm_eps)
        y, st_new = mamba_decode(blk["mamba"], cfg, h, st)
        return x + y, st_new

    x, new_cache = lax.scan(body, x, (params["blocks"], cache))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed_logits(params["embed"], cfg, x)[:, 0]
    return logits, new_cache
