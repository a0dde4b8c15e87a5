"""Encoder–decoder transformer (Whisper family).

The conv audio frontend is a stub per instructions: the encoder consumes
precomputed (B, frames, d_model) frame embeddings (the batch's
``frames``).  Encoder: bidirectional self-attention stack.  Decoder: causal
self-attention (RoPE — adaptation note: Whisper's learned positional
embeddings cap at 448 tokens; RoPE makes the assigned 32k decode shapes
well-defined) + cross-attention to the encoder output + MLP.

Serving: self-attention uses a contiguous KV cache; cross-attention K/V are
computed once from the encoder output at prefill and are static thereafter.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.dist.logical import constrain
from repro.models.common import (
    REMAT_POLICY,
    _qkv,
    apply_rope,
    attend,
    attention_apply,
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
from repro.models.transformer import _stack_inits

__all__ = [
    "init_encdec",
    "encode",
    "encdec_loss",
    "encdec_prefill",
    "encdec_decode_step",
    "encdec_cache_init",
]


def _enc_layer_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    p, s = {}, {}
    p["ln1"], s["ln1"] = rmsnorm_init(cfg.d_model)
    p["attn"], s["attn"] = attention_init(ks[0], cfg)
    p["ln2"], s["ln2"] = rmsnorm_init(cfg.d_model)
    p["mlp"], s["mlp"] = mlp_init(ks[1], cfg)
    return p, s


def _dec_layer_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 3)
    p, s = {}, {}
    p["ln1"], s["ln1"] = rmsnorm_init(cfg.d_model)
    p["self"], s["self"] = attention_init(ks[0], cfg)
    p["ln2"], s["ln2"] = rmsnorm_init(cfg.d_model)
    p["cross"], s["cross"] = attention_init(ks[1], cfg)
    p["ln3"], s["ln3"] = rmsnorm_init(cfg.d_model)
    p["mlp"], s["mlp"] = mlp_init(ks[2], cfg)
    return p, s


def init_encdec(cfg: ModelConfig, key):
    ks = jax.random.split(key, 4)
    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    params["embed"], specs["embed"] = embed_init(ks[0], cfg)
    params["enc_pos"] = 0.02 * jax.random.normal(
        ks[3], (cfg.enc_frames, cfg.d_model), jnp.float32
    )
    specs["enc_pos"] = ("frames", "embed")
    params["enc_blocks"], specs["enc_blocks"] = _stack_inits(
        lambda k: _enc_layer_init(k, cfg), ks[1], cfg.n_enc_layers
    )
    params["enc_norm"], specs["enc_norm"] = rmsnorm_init(cfg.d_model)
    params["dec_blocks"], specs["dec_blocks"] = _stack_inits(
        lambda k: _dec_layer_init(k, cfg), ks[2], cfg.n_layers
    )
    params["final_norm"], specs["final_norm"] = rmsnorm_init(cfg.d_model)
    return params, specs


def encode(params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """frames (B, F, D) — precomputed embeddings from the stub frontend."""
    cdt = compute_dtype(cfg)
    f = frames.shape[1]
    x = frames.astype(cdt) + params["enc_pos"][:f].astype(cdt)[None]
    positions = jnp.arange(f)[None, :]

    def body(x, blk):
        x = constrain(x, "batch", "seq_sp", None)
        h = rmsnorm(x, blk["ln1"], cfg.norm_eps)
        x = x + attention_apply(
            blk["attn"], cfg, h, positions, causal=False, use_rope=False
        )
        h = rmsnorm(x, blk["ln2"], cfg.norm_eps)
        x = x + mlp_apply(blk["mlp"], cfg, h)
        return x, None

    body = jax.checkpoint(body, policy=REMAT_POLICY)
    x, _ = lax.scan(body, x, params["enc_blocks"])
    return rmsnorm(x, params["enc_norm"], cfg.norm_eps)


def _dec_layer(blk, cfg: ModelConfig, x, positions, enc_out):
    h = rmsnorm(x, blk["ln1"], cfg.norm_eps)
    x = x + attention_apply(blk["self"], cfg, h, positions, causal=True)
    h = rmsnorm(x, blk["ln2"], cfg.norm_eps)
    x = x + attention_apply(blk["cross"], cfg, h, positions, kv_from=enc_out)
    h = rmsnorm(x, blk["ln3"], cfg.norm_eps)
    return x + mlp_apply(blk["mlp"], cfg, h)


def encdec_forward(params, cfg: ModelConfig, frames, tokens):
    enc_out = encode(params, cfg, frames)
    x = embed_apply(params["embed"], cfg, tokens)
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]

    def body(x, blk):
        x = constrain(x, "batch", "seq_sp", None)
        return _dec_layer(blk, cfg, x, positions, enc_out), None

    body = jax.checkpoint(body, policy=REMAT_POLICY)
    x, _ = lax.scan(body, x, params["dec_blocks"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return constrain(x, "batch", "seq", None)


def encdec_loss(params, cfg: ModelConfig, frames, tokens, loss_mask=None):
    hidden = encdec_forward(params, cfg, frames, tokens)
    mask = None if loss_mask is None else loss_mask[:, 1:]
    xent = chunked_xent(params["embed"], cfg, hidden[:, :-1], tokens[:, 1:], mask)
    return xent, {"xent": xent, "aux": jnp.zeros((), jnp.float32)}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def encdec_cache_init(cfg: ModelConfig, batch: int, max_len: int):
    hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    cdt = compute_dtype(cfg)
    l = cfg.n_layers
    cache = {
        "self": {
            "k": jnp.zeros((l, batch, hkv, max_len, dh), cdt),
            "v": jnp.zeros((l, batch, hkv, max_len, dh), cdt),
        },
        "cross": {
            "k": jnp.zeros((l, batch, hkv, cfg.enc_frames, dh), cdt),
            "v": jnp.zeros((l, batch, hkv, cfg.enc_frames, dh), cdt),
        },
    }
    spec = jax.tree_util.tree_map(
        lambda _: ("layers", "batch", "kv_heads", None, None), cache
    )
    return cache, spec


def encdec_prefill(params, cfg: ModelConfig, frames, tokens, max_len=None,
                   lengths=None):
    """Encode + decoder forward; builds self- and cross-KV caches."""
    cdt = compute_dtype(cfg)
    enc_out = encode(params, cfg, frames)
    x = embed_apply(params["embed"], cfg, tokens)
    b, s, _ = x.shape
    max_len = max(max_len or s, s)
    positions = jnp.arange(s)[None, :]

    def body(x, blk):

        h = rmsnorm(x, blk["ln1"], cfg.norm_eps)
        q, k, v = _qkv(blk["self"], cfg, h)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        kc, vc = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
        self_kv = {
            "k": jnp.pad(kc, ((0, 0), (0, 0), (0, max_len - s), (0, 0))).astype(cdt),
            "v": jnp.pad(vc, ((0, 0), (0, 0), (0, max_len - s), (0, 0))).astype(cdt),
        }
        att = attend(jnp.swapaxes(q, 1, 2), kc, vc, causal=True)
        att = jnp.swapaxes(att, 1, 2).reshape(b, s, -1)
        x = x + att @ blk["self"]["wo"].astype(cdt)

        h = rmsnorm(x, blk["ln2"], cfg.norm_eps)
        f = enc_out.shape[1]
        hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
        kx = (enc_out @ blk["cross"]["wk"].astype(cdt)).reshape(b, f, hkv, dh)
        vx = (enc_out @ blk["cross"]["wv"].astype(cdt)).reshape(b, f, hkv, dh)
        if cfg.qkv_bias:
            kx = kx + blk["cross"]["bk"].astype(cdt).reshape(hkv, dh)
            vx = vx + blk["cross"]["bv"].astype(cdt).reshape(hkv, dh)
        cross_kv = {
            "k": jnp.swapaxes(kx, 1, 2).astype(cdt),
            "v": jnp.swapaxes(vx, 1, 2).astype(cdt),
        }
        # reuse the cross K/V just computed (§Perf: attention_apply would
        # re-project enc_out, doubling cross-attention prefill compute)
        hq, dh_ = cfg.n_heads, cfg.resolved_head_dim
        qx = (h @ blk["cross"]["wq"].astype(cdt))
        if cfg.qkv_bias:
            qx = qx + blk["cross"]["bq"].astype(cdt)
        qx = qx.reshape(b, s, hq, dh_)
        att_x = attend(
            jnp.swapaxes(qx, 1, 2), cross_kv["k"], cross_kv["v"],
            causal=False,
        )
        att_x = jnp.swapaxes(att_x, 1, 2).reshape(b, s, -1)
        x = x + att_x @ blk["cross"]["wo"].astype(cdt)
        h = rmsnorm(x, blk["ln3"], cfg.norm_eps)
        x = x + mlp_apply(blk["mlp"], cfg, h)
        return x, {"self": self_kv, "cross": cross_kv}

    x, cache = lax.scan(body, x, params["dec_blocks"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = last_token_logits(params["embed"], cfg, x, lengths=lengths)
    return logits, cache


def _cross_decode(p, cfg: ModelConfig, x, cross_kv):
    """One-token cross attention against static K/V (all frames valid)."""
    cdt = compute_dtype(cfg)
    b = x.shape[0]
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = (x[:, 0] @ p["wq"].astype(cdt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)
    q = q.reshape(b, h, dh)
    g = h // hkv
    qg = q.reshape(b, hkv, g, dh).astype(cross_kv["k"].dtype)
    scores = jnp.einsum(
        "bkgd,bksd->bkgs", qg, cross_kv["k"],
        preferred_element_type=jnp.float32,
    ) / math.sqrt(dh)
    pr = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum(
        "bkgs,bksd->bkgd", pr.astype(cross_kv["v"].dtype), cross_kv["v"],
        preferred_element_type=jnp.float32,
    )
    ctx = ctx.reshape(b, h * dh).astype(cdt)
    return (ctx @ p["wo"].astype(cdt))[:, None, :]


def encdec_decode_step(params, cfg: ModelConfig, token, pos, cache):
    x = embed_apply(params["embed"], cfg, token)

    def body(x, xs):
        blk, self_kv, cross_kv = xs
        h = rmsnorm(x, blk["ln1"], cfg.norm_eps)
        att, self_new = attention_decode(blk["self"], cfg, h, pos, self_kv)
        x = x + att
        h = rmsnorm(x, blk["ln2"], cfg.norm_eps)
        x = x + _cross_decode(blk["cross"], cfg, h, cross_kv)
        h = rmsnorm(x, blk["ln3"], cfg.norm_eps)
        x = x + mlp_apply(blk["mlp"], cfg, h)
        return x, self_new

    x, self_new = lax.scan(
        body, x, (params["dec_blocks"], cache["self"], cache["cross"]),
    )
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed_logits(params["embed"], cfg, x)[:, 0]
    return logits, {"self": self_new, "cross": cache["cross"]}
