"""Pure-jnp oracle for one-token GQA decode attention over a paged KV pool.

Gather-then-attend: each slot's blocks are gathered out of the pool into
a contiguous ``(B, Hkv, M*bs, Dh)`` view, whatever its length, and the
math is one einsum, a masked softmax and one einsum over the whole view:

* scores ``q·k / sqrt(Dh)`` with the matmul in the cache dtype and float32
  accumulation (casting the cache to float32 would double what it reads);
* positions at or past the slot's length are masked with ``-1e30``, so
  they contribute exact zeros;
* GQA: query head ``h`` reads KV head ``h // G``, ``G = H // Hkv``.

:func:`decode_attention` is that math over any contiguous cache; the
static engine's contiguous decode uses it too.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["decode_attention", "paged_view", "paged_decode_ref"]

NEG_INF = -1e30


def decode_attention(q, k_cache, v_cache, valid):
    """q (B, H, Dh), k/v_cache (B, Hkv, S, Dh), valid (B, S) bool →
    context (B, Hkv, G, Dh) float32."""
    b, h, dh = q.shape
    hkv = k_cache.shape[1]
    qg = q.reshape(b, hkv, h // hkv, dh).astype(k_cache.dtype)
    s = jnp.einsum(
        "bkgd,bksd->bkgs", qg, k_cache, preferred_element_type=jnp.float32
    )
    s = s / math.sqrt(dh)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bkgs,bksd->bkgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )


def paged_view(pool: jax.Array, tables: jax.Array, block_size: int) -> jax.Array:
    """Gather per-slot contiguous KV views out of one layer's block pool.

    pool (Hkv, P, Dh), tables (B, M) int32 → (B, Hkv, M*bs, Dh).  The
    output shape depends only on the static table width, never on how
    many blocks a slot actually owns.
    """
    b, m = tables.shape
    flat = (
        tables[:, :, None] * block_size
        + jnp.arange(block_size, dtype=tables.dtype)[None, None, :]
    ).reshape(b, m * block_size)
    return jnp.swapaxes(pool[:, flat], 0, 1)  # (B, Hkv, L, Dh)


def paged_decode_ref(
    q: jax.Array,        # (B, H, Dh)
    k_pool: jax.Array,   # (L, Hkv, P, Dh) pools of every layer
    v_pool: jax.Array,
    tables: jax.Array,   # (B, M) int32 block tables
    lengths: jax.Array,  # (B,) int32: positions 0..len-1 are attended
    layer,               # int32 scalar: which layer's pool
    block_size: int,
) -> jax.Array:
    """→ (B, H, Dh) float32."""
    k_cache = paged_view(k_pool[layer], tables, block_size)
    v_cache = paged_view(v_pool[layer], tables, block_size)
    valid = jnp.arange(k_cache.shape[2])[None, :] < lengths[:, None]
    ctx = decode_attention(q, k_cache, v_cache, valid)
    return ctx.reshape(q.shape)
