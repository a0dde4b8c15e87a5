"""Pallas TPU kernel for the SSD inter-chunk state scan.

Grid ``(BH, C)`` with the chunk axis innermost (sequential); the running
state ``h (P, N)`` lives in f32 VMEM scratch across chunk steps.  Each step
emits the prefix state then updates the carry — a single fused
multiply-add over a (P, N) tile (VPU), with the (BH) axis grid-parallel.

The per-chunk decay travels as a ``(BH, C, 1, 1)`` array so its block's
last two dims are full array dims (Mosaic refuses a ``(1, 1)`` block of
a ``(BH, C)`` array); it broadcasts against the state tile in-kernel.

VMEM per step (P=64, N=128): state tile 64×128×4 B = 32 KiB ×3 ≈ 96 KiB ✓
The win vs XLA's unrolled scan: the carry never round-trips to HBM between
chunks — only ``states``/``prefix`` stream through, making the op purely
bandwidth-bound on the chunk tensors.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["ssd_scan_pallas"]


def _ssd_kernel(states_ref, decay_ref, prefix_ref, h_ref):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    h = h_ref[...]
    prefix_ref[0, 0] = h.astype(prefix_ref.dtype)
    d = decay_ref[0, 0]  # (1, 1)
    h_ref[...] = d * h + states_ref[0, 0].astype(jnp.float32)


def ssd_scan_pallas(
    states: jax.Array,  # (BH, C, P, N)
    decay: jax.Array,   # (BH, C)
    interpret: bool = False,
) -> jax.Array:
    bh, c, p, n = states.shape
    if decay.shape != (bh, c):
        raise ValueError(f"decay {decay.shape} != {(bh, c)}")
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        _ssd_kernel,
        grid=(bh, c),
        in_specs=[
            pl.BlockSpec((1, 1, p, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, p, n), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, c, p, n), states.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(states, decay.reshape(bh, c, 1, 1))
