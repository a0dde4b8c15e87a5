"""Public jit'd entry point for flash attention.

TPU → Pallas kernel; elsewhere → the chunked online-softmax in pure XLA
(:func:`~.ref.flash_attention_chunked`).  The Pallas kernel sees whole
arrays: under a mesh the models run it per device
(:func:`repro.models.common.attend`).
"""

from __future__ import annotations

from typing import Optional

import jax

from repro.device import on_tpu

from .kernel import flash_attention_pallas
from .ref import flash_attention_chunked

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
    """TPU → Pallas kernel; elsewhere → chunked online-softmax."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if use_pallas:
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, scale=scale,
            interpret=interpret,
        )
    return flash_attention_chunked(
        q, k, v, causal=causal, window=window, scale=scale
    )

