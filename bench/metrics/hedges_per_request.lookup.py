"""Hedged shard probes the router fired per request in the window
(``QueryService.stats()["fault"]["hedges_fired"]``).  The router hedges
only on its fault-tolerant path, taken once a replica domain has failed."""


def read(ctx):
    before, after = ctx.state.counters["before"], ctx.state.counters["after"]
    requests = ctx.system.requests_in_window(ctx.state)
    if requests <= 0:
        return None
    return (after["hedges_fired"] - before["hedges_fired"]) / requests
