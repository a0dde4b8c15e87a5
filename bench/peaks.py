"""Published peaks of each accelerator the benchmark may run on.

Keyed by JAX's ``device_kind``.  A device missing from the table is an
error: a roofline share against a guessed peak is no measurement.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["Peaks", "peaks_for"]


@dataclass(frozen=True)
class Peaks:
    """The bounds a roofline share is taken against.  No vector-unit peak
    is published for v5e, so kernels without matmuls (comparisons,
    popcounts) are held to their HBM bound alone."""

    bf16_flops: float           # dense matmul, FLOP/s
    hbm_bytes_per_s: float
    source: str


_V5E = Peaks(
    bf16_flops=197e12,
    hbm_bytes_per_s=819e9,
    source="Google Cloud documentation, 'TPU v5e' (system architecture): "
           "197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s",
)

_TABLE = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return _TABLE[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add them "
            f"to bench/peaks.py with their source"
        ) from None
