"""Mean keys per coalesced MicroBatcher batch over the window
(``QueryService.stats()["scheduler"]``: keys / batches)."""


def read(ctx):
    before, after = ctx.state.counters["before"], ctx.state.counters["after"]
    batches = after["batches"] - before["batches"]
    if batches <= 0:
        return None
    return (after["batch_keys"] - before["batch_keys"]) / batches
