"""Hybrid SSM + attention model (Jamba family).

Super-block of ``hybrid_block`` layers scanned ``n_layers/hybrid_block``
times: position ``attn_index`` is GQA attention, the rest are Mamba2 SSD
mixers; the FFN alternates dense MLP (even positions) and MoE (odd
positions), reproducing Jamba's every-other-layer MoE placement.

Decode cost: only one attention layer per 8 carries a growing KV cache —
the reason this arch runs the long_500k cell.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.dist.logical import constrain
from repro.models import moe as moe_mod
from repro.models.common import (
    REMAT_POLICY,
    RESIDUAL_AXES,
    attend,
    attention_decode,
    attention_init,
    chunked_xent,
    compute_dtype,
    embed_apply,
    embed_init,
    mlp_apply,
    mlp_init,
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
    "init_hybrid",
    "hybrid_forward",
    "hybrid_loss",
    "hybrid_prefill",
    "hybrid_decode_step",
    "hybrid_cache_init",
]

PyTree = Any


def _layout(cfg: ModelConfig):
    per = cfg.hybrid_block
    n_blocks = cfg.n_layers // per
    assert cfg.n_layers % per == 0
    mamba_pos = [j for j in range(per) if j != cfg.attn_index]
    moe_pos = [j for j in range(per) if j % cfg.moe_every == cfg.moe_every - 1]
    mlp_pos = [j for j in range(per) if j not in moe_pos]
    return n_blocks, per, mamba_pos, moe_pos, mlp_pos


def _block_init(key, cfg: ModelConfig):
    n_blocks, per, mamba_pos, moe_pos, mlp_pos = _layout(cfg)
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {}
    s: Dict[str, Any] = {}

    def stack(fn, k, n):
        kk = jax.random.split(k, n)
        ps, ss = zip(*[fn(kk[i]) for i in range(n)])
        return (
            jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ps),
            jax.tree_util.tree_map(
                lambda sp: ("block_pos",) + tuple(sp),
                ss[0],
                is_leaf=lambda x: isinstance(x, tuple),
            ),
        )

    p["mamba"], s["mamba"] = stack(lambda k: mamba_init(k, cfg), ks[0], len(mamba_pos))
    p["attn"], s["attn"] = attention_init(ks[1], cfg)
    if moe_pos:
        p["moe"], s["moe"] = stack(lambda k: moe_mod.moe_init(k, cfg), ks[2], len(moe_pos))
    if mlp_pos:
        p["mlp"], s["mlp"] = stack(lambda k: mlp_init(k, cfg), ks[3], len(mlp_pos))
    p["ln_mix"] = jnp.ones((per, cfg.d_model), jnp.float32)
    p["ln_ffn"] = jnp.ones((per, cfg.d_model), jnp.float32)
    s["ln_mix"] = ("block_pos", "embed_act")
    s["ln_ffn"] = ("block_pos", "embed_act")
    return p, s


def init_hybrid(cfg: ModelConfig, key) -> Tuple[PyTree, PyTree]:
    n_blocks, *_ = _layout(cfg)
    ks = jax.random.split(key, 2)
    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    params["embed"], specs["embed"] = embed_init(ks[0], cfg)
    params["blocks"], specs["blocks"] = _stack_inits(
        lambda k: _block_init(k, cfg), ks[1], n_blocks
    )
    params["final_norm"], specs["final_norm"] = rmsnorm_init(cfg.d_model)
    return params, specs


def _apply_block(blk, cfg: ModelConfig, x, positions, no_drop=False):
    """One super-block (full sequence).  Returns (x, aux)."""
    _, per, mamba_pos, moe_pos, mlp_pos = _layout(cfg)
    aux = jnp.zeros((), jnp.float32)
    mi = ai = fi_moe = fi_mlp = 0
    for j in range(per):
        h = rmsnorm(x, blk["ln_mix"][j], cfg.norm_eps)
        if j == cfg.attn_index:
            from repro.models.common import attention_apply

            x = x + attention_apply(blk["attn"], cfg, h, positions, causal=True)
        else:
            mp = jax.tree_util.tree_map(lambda v: v[mi], blk["mamba"])
            x = x + mamba_apply(mp, cfg, h)
            mi += 1
        h = rmsnorm(x, blk["ln_ffn"][j], cfg.norm_eps)
        if j in moe_pos:
            ep = jax.tree_util.tree_map(lambda v: v[fi_moe], blk["moe"])
            y, a = moe_mod.moe_apply(ep, cfg, h, no_drop=no_drop)
            aux = aux + a
            fi_moe += 1
        else:
            lp = jax.tree_util.tree_map(lambda v: v[fi_mlp], blk["mlp"])
            y = mlp_apply(lp, cfg, h)
            fi_mlp += 1
        x = x + y
    return x, aux


def hybrid_forward(params, cfg: ModelConfig, tokens: jax.Array):
    x = embed_apply(params["embed"], cfg, tokens)
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]

    def body(carry, blk):
        x, aux = carry
        x = constrain(x, "batch", "seq_sp", None)
        x, a = _apply_block(blk, cfg, x, positions)
        return (x, aux + a), None

    body = jax.checkpoint(body, policy=REMAT_POLICY)
    (x, aux), _ = lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), params["blocks"],
    )
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return constrain(x, "batch", "seq", None), aux


def hybrid_loss(params, cfg: ModelConfig, tokens, loss_mask=None):
    hidden, aux = hybrid_forward(params, cfg, tokens)
    mask = None if loss_mask is None else loss_mask[:, 1:]
    xent = chunked_xent(params["embed"], cfg, hidden[:, :-1], tokens[:, 1:], mask)
    return xent + cfg.router_aux_coef * aux, {"xent": xent, "aux": aux}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def hybrid_cache_init(cfg: ModelConfig, batch: int, max_len: int):
    n_blocks, per, mamba_pos, *_ = _layout(cfg)
    hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    cdt = compute_dtype(cfg)
    one_state = mamba_state_init(cfg, batch, cdt)
    cache = {
        "attn": {
            "k": jnp.zeros((n_blocks, batch, hkv, max_len, dh), cdt),
            "v": jnp.zeros((n_blocks, batch, hkv, max_len, dh), cdt),
        },
        "mamba": jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(
                a[None, None], (n_blocks, len(mamba_pos)) + a.shape
            ),
            one_state,
        ),
    }
    spec = {
        "attn": {
            "k": ("layers", "batch", "kv_heads", None, None),
            "v": ("layers", "batch", "kv_heads", None, None),
        },
        # ssm (nb, nm, B, H, P, N); conv (nb, nm, B, K-1, conv_dim)
        "mamba": {
            "ssm": ("layers", "block_pos", "batch", "ssm_heads", None, None),
            "conv": ("layers", "block_pos", "batch", None, "conv_dim"),
        },
    }
    return cache, spec


def hybrid_prefill(params, cfg: ModelConfig, tokens, max_len: Optional[int] = None,
                   lengths=None):
    """Forward + cache build.  Attention KV padded to ``max_len``."""
    cdt = compute_dtype(cfg)
    x = embed_apply(params["embed"], cfg, tokens)
    b, s, _ = x.shape
    max_len = max(max_len or s, s)
    positions = jnp.arange(s)[None, :]
    _, per, mamba_pos, moe_pos, mlp_pos = _layout(cfg)

    def body(x, blk):
        from repro.models.common import _qkv, apply_rope

        aux = jnp.zeros((), jnp.float32)
        mi = fi_moe = fi_mlp = 0
        kv_out = None
        mamba_states = []
        for j in range(per):
            h = rmsnorm(x, blk["ln_mix"][j], cfg.norm_eps)
            if j == cfg.attn_index:
                q, k, v = _qkv(blk["attn"], cfg, h)
                q = apply_rope(q, positions, cfg.rope_theta)
                k = apply_rope(k, positions, cfg.rope_theta)
                kc, vc = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
                kv_out = {
                    "k": jnp.pad(kc, ((0, 0), (0, 0), (0, max_len - s), (0, 0))).astype(cdt),
                    "v": jnp.pad(vc, ((0, 0), (0, 0), (0, max_len - s), (0, 0))).astype(cdt),
                }
                att = attend(jnp.swapaxes(q, 1, 2), kc, vc, causal=True)
                att = jnp.swapaxes(att, 1, 2).reshape(b, s, -1)
                x = x + constrain(
                    att @ blk["attn"]["wo"].astype(cdt), *RESIDUAL_AXES
                )
            else:
                mp = jax.tree_util.tree_map(lambda v: v[mi], blk["mamba"])
                y, st = mamba_apply(mp, cfg, h, return_state=True)
                x = x + y
                mamba_states.append(st)
                mi += 1
            h = rmsnorm(x, blk["ln_ffn"][j], cfg.norm_eps)
            if j in moe_pos:
                ep = jax.tree_util.tree_map(lambda v: v[fi_moe], blk["moe"])
                y, _ = moe_mod.moe_apply(ep, cfg, h)
                fi_moe += 1
            else:
                lp = jax.tree_util.tree_map(lambda v: v[fi_mlp], blk["mlp"])
                y = mlp_apply(lp, cfg, h)
                fi_mlp += 1
            x = x + y
        stacked_states = jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *mamba_states
        )
        return x, {"attn": kv_out, "mamba": stacked_states}

    x, cache = lax.scan(body, x, params["blocks"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = last_token_logits(params["embed"], cfg, x, lengths=lengths)
    return logits, cache


def hybrid_decode_step(params, cfg: ModelConfig, token, pos, cache):
    """One-token decode.  token (B,1), pos (B,)."""
    x = embed_apply(params["embed"], cfg, token)
    _, per, mamba_pos, moe_pos, mlp_pos = _layout(cfg)

    def body(x, xs):
        blk, kv, mstates = xs
        mi = fi_moe = fi_mlp = 0
        new_m = []
        for j in range(per):
            h = rmsnorm(x, blk["ln_mix"][j], cfg.norm_eps)
            if j == cfg.attn_index:
                att, kv_new = attention_decode(blk["attn"], cfg, h, pos, kv)
                x = x + att
            else:
                mp = jax.tree_util.tree_map(lambda v: v[mi], blk["mamba"])
                st = jax.tree_util.tree_map(lambda v: v[mi], mstates)
                y, st_new = mamba_decode(mp, cfg, h, st)
                x = x + y
                new_m.append(st_new)
                mi += 1
            h = rmsnorm(x, blk["ln_ffn"][j], cfg.norm_eps)
            if j in moe_pos:
                ep = jax.tree_util.tree_map(lambda v: v[fi_moe], blk["moe"])
                y, _ = moe_mod.moe_apply(ep, cfg, h, no_drop=True)
                fi_moe += 1
            else:
                lp = jax.tree_util.tree_map(lambda v: v[fi_mlp], blk["mlp"])
                y = mlp_apply(lp, cfg, h)
                fi_mlp += 1
            x = x + y
        new_mamba = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *new_m)
        return x, (kv_new, new_mamba)

    x, (kv_new, m_new) = lax.scan(
        body, x, (params["blocks"], cache["attn"], cache["mamba"]),
    )
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed_logits(params["embed"], cfg, x)[:, 0]
    return logits, {"attn": kv_new, "mamba": m_new}
