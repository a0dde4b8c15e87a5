"""Flash attention kernel time against the larger of its bf16 matmul
bound and its HBM bound, in %, over the traced window: the useful work of
the prompts whose first token came inside the window
(:func:`bench.costs.flash_attention.prompt_cost`) over the time of the
kernel's runs there."""

from bench.costs.flash_attention import is_flash, prompt_cost


def read(ctx):
    t = ctx.trace_data
    if t is None:
        return None
    lo, hi = t.window
    spent = sum(k.seconds for k in t.kernels if lo <= k.start <= hi and is_flash(k))
    prompts = ctx.system.traced_prompts(ctx.state)
    if spent <= 0 or not prompts:
        return None
    least = 0.0
    for n in prompts:
        flops, moved = prompt_cost(ctx.state.model, n)
        least += max(flops / ctx.peaks.bf16_flops, moved / ctx.peaks.hbm_bytes_per_s)
    return 100.0 * least / spent
