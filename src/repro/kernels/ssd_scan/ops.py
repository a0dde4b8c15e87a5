"""Public jit'd entry point for the SSD inter-chunk scan."""

from __future__ import annotations

import functools

import jax

from repro.device import on_tpu

from .kernel import ssd_scan_pallas
from .ref import ssd_scan_ref

__all__ = ["ssd_scan"]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def ssd_scan(
    states: jax.Array,
    decay: jax.Array,
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> jax.Array:
    if use_pallas is None:
        use_pallas = on_tpu()
    if use_pallas:
        return ssd_scan_pallas(states, decay, interpret=interpret)
    return ssd_scan_ref(states, decay)
