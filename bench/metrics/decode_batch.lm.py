"""Mean tokens each batched decode step kept over the window
(``ContinuousStats``: decode tokens / steps; at most the slot count)."""


def read(ctx):
    before, after = ctx.state.counters["before"], ctx.state.counters["after"]
    steps = after["steps"] - before["steps"]
    if steps <= 0:
        return None
    return (after["decode_tokens"] - before["decode_tokens"]) / steps
