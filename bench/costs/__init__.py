"""Operations and bytes of each kernel, computed from its shapes.

One module per kernel, named as the kernel's jitted entry point is named
in the trace.  ``cost(kernel) -> (flops, bytes)`` gives one run's work
from its shapes (:class:`bench.tracing.Kernel`): ``flops`` are the
useful matmul FLOPs the algorithm needs (``None`` for a kernel with no
matmul, whose vector-unit peak v5e does not publish), ``bytes`` the
fewest HBM bytes it must move, each input read once and each output
written once.  Flash attention's padded operands hide the true prompt
length, so its module costs a prompt instead (``prompt_cost``).  All are
lower bounds of the work, so the least time they imply can never exceed
the time the kernel took.  :mod:`bench.costs.dense_lm` gives a whole
model step's FLOPs for the ``mfu`` metrics.
"""

from __future__ import annotations

from typing import Callable, List, Optional

_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8}


def nbytes(shapes) -> int:
    total = 0
    for dtype, dims in shapes:
        n = 1
        for d in dims:
            n *= d
        total += n * _BYTES[dtype]
    return total


def roofline_share(kernels: List, cost: Callable, peaks) -> Optional[float]:
    """Least time over measured time, in %, summed over ``kernels`` runs;
    None when there is no run to read."""
    spent = sum(k.seconds for k in kernels)
    if not kernels or spent <= 0:
        return None
    least = 0.0
    for k in kernels:
        flops, moved = cost(k)
        t = moved / peaks.hbm_bytes_per_s
        if flops is not None:
            t = max(t, flops / peaks.bf16_flops)
        least += t
    return 100.0 * least / spent
