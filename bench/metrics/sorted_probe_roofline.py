"""``sorted_probe_pallas`` time against its HBM bound (no matmul; v5e
publishes no vector-unit peak), in % over the traced window."""

from bench.costs import roofline_share
from bench.costs.sorted_probe_pallas import cost


def read(ctx):
    if ctx.trace_data is None:
        return None
    return roofline_share(ctx.trace_data.kernel_runs("sorted_probe_pallas"),
                          cost, ctx.peaks)
