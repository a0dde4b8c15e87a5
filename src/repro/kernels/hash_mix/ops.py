"""Public jit'd entry points for ``hash_mix``.

``hash_mix(x)`` dispatches to the Pallas kernel on TPU and to the pure-jnp
reference elsewhere (on a CPU the kernel runs only under
``interpret=True``, in tests — Mosaic lowering is TPU-only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.device import on_tpu

from .kernel import hash_mix_pallas
from .ref import hash_mix_ref

__all__ = ["hash_mix", "hash_mix_u64", "digest_ids"]


@functools.partial(jax.jit, static_argnames=("seed", "use_pallas", "interpret"))
def hash_mix(
    x: jax.Array,
    seed: int = 0,
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> jax.Array:
    """``(N, W) uint32 → (N, 4) uint32`` digest (see kernel/ref)."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if use_pallas:
        return hash_mix_pallas(x, seed=seed, interpret=interpret)
    return hash_mix_ref(x, seed=seed)


def hash_mix_u64(x: jax.Array, seed: int = 0) -> jax.Array:
    """First 64 digest bits as ``(N, 2) uint32`` (hi, lo) pairs.

    The sorted-probe membership path keys on 64-bit digests; collisions at
    that width degrade to an extra full-id verify, never to wrong results.
    """
    d = hash_mix(x, seed=seed)
    return d[:, :2]


def digest_ids(ids, seed: int = 0) -> np.ndarray:
    """Host convenience: list[str] → (N, 2) uint32 digests via packing."""
    from repro.core.packing import pack_ids

    packed = jnp.asarray(pack_ids(list(ids)))
    return np.asarray(hash_mix_u64(packed, seed=seed))
