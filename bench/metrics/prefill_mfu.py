"""Useful prefill FLOPs over the device time of the prefill programs, as
a share of the chip's bf16 peak, %.  FLOPs: every request whose first
token came inside the traced window, over its real (unpadded) prompt
(:mod:`bench.costs.dense_lm`).  Time: the device runs, in the traced
window, of the programs that contain a flash attention kernel (only the
prefill calls it; decode attends through the paged view)."""

import bisect

from bench.costs.dense_lm import prefill_flops
from bench.costs.flash_attention import is_flash


def read(ctx):
    t = ctx.trace_data
    if t is None:
        return None
    lo, hi = t.window
    starts = sorted(k.start for k in t.kernels if lo <= k.start <= hi and is_flash(k))
    seconds = 0.0
    for mod in t.modules[0]:
        if not (lo <= mod.start <= hi):
            continue
        i = bisect.bisect_left(starts, mod.start)
        if i < len(starts) and starts[i] <= mod.end:
            seconds += mod.end - mod.start
    flops = sum(prefill_flops(ctx.state.model, n)
                for n in ctx.system.traced_prompts(ctx.state))
    if seconds <= 0 or flops <= 0:
        return None
    return 100.0 * flops / seconds / ctx.peaks.bf16_flops
