"""Pure-jnp oracle for causal/windowed GQA flash attention.

Unblocked reference: materializes the full (Sq, Skv) score matrix in f32.
Semantics shared with the kernel:

* queries are the **last** ``Sq`` positions of the key sequence (so
  prefill Sq == Skv and decode Sq == 1 both work with one offset rule);
* ``causal``: key position must be <= query position;
* ``window``: if set, key position must be > query position - window
  (sliding-window attention — Gemma3 local layers, window=1024);
* GQA: Hq queries share Hkv key/value heads (Hq % Hkv == 0).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["flash_attention_ref", "flash_attention_chunked"]

NEG_INF = -1e30


def flash_attention_ref(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Skv, D)
    v: jax.Array,  # (B, Hkv, Skv, D)
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    g = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # expand kv heads for GQA
    kf = jnp.repeat(kf, g, axis=1)
    vf = jnp.repeat(vf, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
    q_pos = jnp.arange(sq)[:, None] + (skv - sq)  # query abs position
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    return out.astype(q.dtype)


def flash_attention_chunked(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Skv, D)
    v: jax.Array,  # (B, Hkv, Skv, D)
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    chunk: int = 1024,
) -> jax.Array:
    """Online-softmax attention in pure XLA (flash attention without Pallas).

    Scans over KV chunks carrying (m, l, acc), so peak score memory is
    (B, H, Sq, chunk) instead of the (B, H, Sq, Skv) that
    :func:`flash_attention_ref` materializes.  K and V are repeated to the
    query heads one chunk at a time.  Matmuls run in the input dtype with
    f32 accumulation (MXU-native).
    """
    from repro.dist.logical import constrain

    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    g = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    c = min(chunk, skv)
    pad = (c - skv % c) % c
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nkc = (skv + pad) // c
    off = skv - sq  # queries are the last sq positions
    # FULL-HEAD layout: hq stays a shardable TP dim (heads→model).  K/V are
    # repeated to hq heads PER CHUNK inside the scan body (chunk-sized, so
    # the repeat costs ~nothing) — the grouped (B,Hkv,G,…) form would make
    # both head dims indivisible by the model axis and silently replicate
    # the whole attention computation (measured: +3× bytes on qwen3-moe).
    qf = q * jnp.asarray(scale, q.dtype)
    qf = constrain(qf, "batch", "heads", None, None)
    q_pos = jnp.arange(sq) + off

    kc = k.reshape(b, hkv, nkc, c, d).transpose(2, 0, 1, 3, 4)  # (n,B,Hkv,c,D)
    vc = v.reshape(b, hkv, nkc, c, d).transpose(2, 0, 1, 3, 4)

    def step(carry, xs):
        m_prev, l_prev, acc = carry
        kb, vb, ci = xs
        kb = jnp.repeat(kb, g, axis=1)      # (B, Hq, c, D) chunk-local
        vb = jnp.repeat(vb, g, axis=1)
        kb = constrain(kb, "batch", "heads", None, None)
        vb = constrain(vb, "batch", "heads", None, None)
        s = jnp.einsum(
            "bhqd,bhcd->bhqc", qf, kb,
            preferred_element_type=jnp.float32,
        )  # (B, Hq, Sq, c)
        k_pos = ci * c + jnp.arange(c)
        mask = (k_pos[None, :] < skv)
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = p * mask[None, None]
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqc,bhcd->bhqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    a0 = constrain(
        jnp.zeros((b, hq, sq, d), jnp.float32), "batch", "heads", None, None
    )
    (m_f, l_f, acc), _ = lax.scan(
        step, (m0, l0, a0),
        (kc, vc, jnp.arange(nkc)),
    )
    l_safe = jnp.where(l_f > 0, l_f, 1.0)
    out = acc / l_safe[..., None]
    return out.astype(q.dtype)
