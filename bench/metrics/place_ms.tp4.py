"""Host time a decode step spends putting its inputs on the mesh, ms:
Δ``ContinuousStats.place_s`` ÷ Δ``steps`` over the window (the
``engine.place`` span's work; 0 where there is no mesh)."""


def read(ctx):
    before, after = ctx.state.counters["before"], ctx.state.counters["after"]
    if "place_s" not in before or "place_s" not in after:
        return None
    steps = after["steps"] - before["steps"]
    if steps <= 0:
        return None
    return 1e3 * (after["place_s"] - before["place_s"]) / steps
