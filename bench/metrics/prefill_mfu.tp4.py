"""Useful prefill FLOPs over the device time of the prefill programs
summed over the cell's devices, as a share of one chip's bf16 peak, %
(of the devices' summed peak).  FLOPs as ``prefill_mfu``'s: every request
whose first token came inside the traced window, over its real prompt.
Time: on each device, the runs in the traced window of the programs that
hold a flash attention kernel on that device (only prefill calls it).
``prefill_mfu`` reads device 0 alone, which would read about four times
high on four devices."""

import bisect

from bench.costs.dense_lm import prefill_flops
from bench.costs.flash_attention import is_flash
from bench.tracing import _kernel


def prefill_seconds(trace) -> float:
    lo, hi = trace.window
    seconds = 0.0
    for ops, mods in zip(trace.ops, trace.modules):
        starts = sorted(op.start for op in ops if lo <= op.start <= hi
                        and (k := _kernel(op)) is not None and is_flash(k))
        for mod in mods:
            if not (lo <= mod.start <= hi):
                continue
            i = bisect.bisect_left(starts, mod.start)
            if i < len(starts) and starts[i] <= mod.end:
                seconds += mod.end - mod.start
    return seconds


def read(ctx):
    t = ctx.trace_data
    if t is None:
        return None
    seconds = prefill_seconds(t)
    flops = sum(prefill_flops(ctx.state.model, n)
                for n in ctx.system.traced_prompts(ctx.state))
    if seconds <= 0 or flops <= 0:
        return None
    return 100.0 * flops / seconds / ctx.peaks.bf16_flops
