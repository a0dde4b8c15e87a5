"""Public jit'd entry point for flash attention.

TPU → Pallas kernel; elsewhere → pure-jnp reference (XLA fuses it well
enough for CPU tests, and the dry-run rooflines measure the XLA path).
The Pallas kernel sees whole arrays: under a mesh the models run it per
device (:func:`repro.models.common.attend`).
"""

from __future__ import annotations

from typing import Optional

import jax

from repro import flags
from repro.device import on_tpu

from .kernel import flash_attention_pallas
from .ref import flash_attention_chunked, flash_attention_ref

__all__ = ["flash_attention"]


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> jax.Array:
    """TPU → Pallas kernel; XLA path → chunked online-softmax (default)
    or the unblocked reference (REPRO_ATTN_IMPL=ref, §Perf baseline)."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if use_pallas:
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, scale=scale,
            interpret=interpret,
        )
    if flags.ATTN_IMPL == "chunked":
        return flash_attention_chunked(
            q, k, v, causal=causal, window=window, scale=scale,
            chunk=flags.ATTN_CHUNK,
        )
    return flash_attention_ref(q, k, v, causal=causal, window=window, scale=scale)

