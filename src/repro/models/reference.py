"""Plain float32 reference forward for the dense GQA transformer family.

Written independently of :mod:`repro.models.transformer`: one Python loop
over layers, full causal softmax attention with repeated KV heads, the
SwiGLU MLP, all in float32 under ``jax.default_matmul_precision
("highest")`` — no scan, no cache, no flash kernel, no sharding rules,
no compute-dtype casts.  Serving paths (prefill, paged decode, suffix
prefill) are checked against it within a tolerance that covers their
bfloat16 compute.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

__all__ = ["dense_lm_logits"]


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (S, H, Dh): rotate the two halves of each head by pos * freq."""
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freq      # (S, 1, half)
    x1, x2 = x[..., :half], x[..., half:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def dense_lm_logits(params: Any, cfg: ModelConfig, tokens: jax.Array,
                    rows: jax.Array) -> jax.Array:
    """Logits ``(len(rows), V)`` at positions ``rows`` of one ``(S,)``
    token sequence, computed in float32 at the highest matmul precision."""
    if cfg.family != "dense" or cfg.window is not None:
        raise ValueError(f"reference covers dense full-attention stacks, "
                         f"not {cfg.family!r} (window={cfg.window})")
    f32 = jnp.float32
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]                   # (q, k)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"].astype(f32)[tokens]    # (S, D)
        for layer in range(cfg.n_layers):
            p = jax.tree_util.tree_map(lambda a: a[layer].astype(f32),
                                       params["blocks"])
            a = p["attn"]
            y = _rms(x, p["ln1"], cfg.norm_eps)
            q, k, v = y @ a["wq"], y @ a["wk"], y @ a["wv"]
            if cfg.qkv_bias:
                q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
            q = _rope(q.reshape(s, h, dh), pos, cfg.rope_theta)
            k = _rope(k.reshape(s, hkv, dh), pos, cfg.rope_theta)
            v = v.reshape(s, hkv, dh)
            k = jnp.repeat(k, h // hkv, axis=1)             # head i -> kv i // g
            v = jnp.repeat(v, h // hkv, axis=1)
            sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(f32(dh))
            w = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
            o = jnp.einsum("hqk,khd->qhd", w, v).reshape(s, h * dh)
            x = x + o @ a["wo"]
            m = p["mlp"]
            y = _rms(x, p["ln2"], cfg.norm_eps)
            x = x + (jax.nn.silu(y @ m["wg"]) * (y @ m["wu"])) @ m["wd"]
        x = _rms(x[rows], params["final_norm"].astype(f32), cfg.norm_eps)
        emb = params["embed"]
        w_out = emb["table"].T if cfg.tie_embeddings else emb["unembed"]
        return x @ w_out.astype(f32)
