"""Pallas TPU kernel for ``hash_mix``: blocked 128-bit mixing digest.

Lane-dense layout: the wrapper transposes the ``(N, W)`` uint32 input to
``(W, R, 128)`` (identifier ``r * 128 + j`` sits at sublane ``r``, lane
``j``), so every identifier lane ``i`` of a block is one full
``(BR, 128)`` tile and the four running hash words are ``(BR, 128)``
tiles too.  The mix is pure VPU integer arithmetic over whole vregs —
no lane extraction, no relayout, no MXU.  Block rows are
grid-parallel; the lane loop is unrolled at trace time (W is static and
small: identifiers pack into ≤ 64 lanes).

VMEM budget per grid step (BR=32 → 4096 ids, W=64):
  in  64 × 32 × 128 × 4 B = 1 MiB (×2 double-buffered)
  out  4 × 32 × 128 × 4 B = 64 KiB                « 16 MiB scoped ✓
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import PRIME1, PRIME2, PRIME3, PRIME4

__all__ = ["hash_mix_pallas", "DEFAULT_BLOCK_ROWS"]

LANES = 128
SUBLANES = 8
DEFAULT_BLOCK_ROWS = 4096  # identifiers per grid step


def _rotl(x, r: int):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def _avalanche(h):
    h = h ^ (h >> jnp.uint32(15))
    h = h * PRIME2
    h = h ^ (h >> jnp.uint32(13))
    h = h * PRIME3
    h = h ^ (h >> jnp.uint32(16))
    return h


def _hash_mix_kernel(x_ref, out_ref, *, w: int, seed: int):
    shape = x_ref.shape[1:]  # (BR, 128): one tile per identifier lane
    s = jnp.uint32(seed)
    h0 = jnp.full(shape, PRIME1 + s, dtype=jnp.uint32)
    h1 = jnp.full(shape, PRIME2 ^ s, dtype=jnp.uint32)
    h2 = jnp.full(shape, PRIME3 + (s * PRIME1), dtype=jnp.uint32)
    h3 = jnp.full(shape, PRIME4 ^ (s * PRIME2), dtype=jnp.uint32)
    for i in range(w):  # static unroll over identifier lanes
        k = x_ref[i]
        lane = jnp.uint32(i + 1)
        h0 = _rotl(h0 + k * PRIME2, 13) * PRIME1
        h1 = _rotl(h1 ^ (k + lane) * PRIME3, 17) * PRIME2
        h2 = _rotl(h2 + (k ^ lane * PRIME1) * PRIME4, 11) * PRIME3
        h3 = _rotl(h3 ^ k * PRIME1, 19) * PRIME4
    ln = jnp.uint32(w)
    h0 = _avalanche(h0 ^ (ln * PRIME1) ^ _rotl(h1, 7))
    h1 = _avalanche(h1 ^ (ln * PRIME2) ^ _rotl(h2, 12))
    h2 = _avalanche(h2 ^ (ln * PRIME3) ^ _rotl(h3, 18))
    h3 = _avalanche(h3 ^ (ln * PRIME4) ^ _rotl(h0, 23))
    out_ref[0] = h0
    out_ref[1] = h1
    out_ref[2] = h2
    out_ref[3] = h3


def hash_mix_pallas(
    x: jax.Array,
    seed: int = 0,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jax.Array:
    """Blocked Pallas digest; bit-exact vs :func:`..ref.hash_mix_ref`.

    ``block_rows`` identifiers go through each grid step, rounded up to
    whole ``(8, 128)`` tiles; an input that fits in one step runs as a
    single full-array block.  ``N`` is zero-padded up to the grid
    (padded identifiers hash garbage zeros and are sliced off — digests
    are row-local so padding cannot contaminate real rows).
    """
    if x.dtype != jnp.uint32 or x.ndim != 2:
        raise TypeError(f"expected (N, W) uint32, got {x.shape} {x.dtype}")
    n, w = x.shape
    rows = -(-n // LANES)                      # 128-id lane rows needed
    br = -(-max(block_rows, 1) // (LANES * SUBLANES)) * SUBLANES
    if rows <= br:
        br = rows                              # one full-array block
    r_pad = -(-rows // br) * br
    n_pad = r_pad * LANES
    xp = jnp.pad(x, ((0, n_pad - n), (0, 0))) if n_pad != n else x
    xt = xp.T.reshape(w, r_pad, LANES)
    out = pl.pallas_call(
        functools.partial(_hash_mix_kernel, w=w, seed=seed),
        grid=(r_pad // br,),
        in_specs=[pl.BlockSpec((w, br, LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((4, br, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((4, r_pad, LANES), jnp.uint32),
        interpret=interpret,
    )(xt)
    return out.reshape(4, n_pad).T[:n]
