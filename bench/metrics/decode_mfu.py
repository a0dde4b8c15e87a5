"""Useful decode FLOPs over the device time of the decode step, as a share
of the chip's bf16 peak, %.  FLOPs: decode tokens the engine emitted
while traced (``ContinuousStats.decode_tokens``) times the FLOPs of one
decoded token at the mix's mean context (:mod:`bench.costs.dense_lm`).
Time: the device runs, in the traced window, of the engine's jitted
decode step (its program ``jit_step`` on the trace's ``XLA Modules``
line), summed over the devices the cell uses."""

import numpy as np

from bench.costs.dense_lm import decode_flops

DECODE_PROGRAM = "jit_step"


def mean_context(streams) -> float:
    """Mean attended length of a decode token over the mix's requests."""
    total = count = 0
    for s in streams:
        for prompt, out, _ in np.asarray(s, np.int64):
            k = np.arange(1, out)            # decode token k sees prompt + k keys
            total += float((prompt + k).sum())
            count += len(k)
    return total / count if count else 0.0


def decode_seconds(trace) -> float:
    """Device time of the decode step's runs, clipped to the window."""
    lo, hi = trace.window
    return sum(max(0.0, min(m.end, hi) - max(m.start, lo))
               for mods in trace.modules for m in mods if m.name == DECODE_PROGRAM)


def read(ctx):
    t, c = ctx.trace_data, ctx.state.counters
    if t is None or "trace_start" not in c or "trace_stop" not in c:
        return None
    tokens = c["trace_stop"]["decode_tokens"] - c["trace_start"]["decode_tokens"]
    seconds = decode_seconds(t)
    if tokens <= 0 or seconds <= 0:
        return None
    flops = tokens * decode_flops(ctx.state.model, mean_context(ctx.state.streams))
    return 100.0 * flops / seconds / ctx.peaks.bf16_flops
