"""Public entry point for paged decode attention.

TPU → Pallas kernel, where its tiles fit the shapes; elsewhere, and for
shapes they do not fit, → the gather-then-attend reference.  The Pallas
kernel sees whole arrays: under a mesh the models run it per device
(:func:`repro.models.common.paged_attend`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.device import on_tpu

from .kernel import paged_decode_pallas
from .ref import paged_decode_ref

__all__ = ["paged_decode", "tiles_fit"]


def tiles_fit(head_dim: int, block_size: int, dtype) -> bool:
    """Whether the compiled kernel's tiles fit: whole 128-lane rows of
    head_dim, and blocks of whole sublane tiles (8 rows of 32 bits)."""
    rows = 8 * 4 // jnp.dtype(dtype).itemsize
    return head_dim % 128 == 0 and block_size % rows == 0


def paged_decode(
    q: jax.Array,        # (B, H, Dh)
    k_pool: jax.Array,   # (L, Hkv, P, Dh)
    v_pool: jax.Array,
    tables: jax.Array,   # (B, M) int32
    lengths: jax.Array,  # (B,) int32, each >= 1
    layer,               # int32 scalar
    block_size: int,
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> jax.Array:
    """One-token attention of each slot over its first ``lengths`` pool
    positions of ``layer`` → (B, H, Dh) float32."""
    if use_pallas is None:
        use_pallas = on_tpu() and tiles_fit(
            q.shape[-1], block_size, k_pool.dtype)
    if use_pallas:
        return paged_decode_pallas(
            q, k_pool, v_pool, tables, lengths, layer, block_size,
            interpret=interpret,
        )
    return paged_decode_ref(q, k_pool, v_pool, tables, lengths, layer,
                            block_size)
