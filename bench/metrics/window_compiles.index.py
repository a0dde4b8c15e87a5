"""Programs lowered inside the window (compiled, or loaded from the
persistent cache): each one stalls the request that needed it.  Read
from JAX's compile events; every shape should have been warmed, so 0 is
the expected reading."""


def read(ctx):
    return float(ctx.window_compiles)
