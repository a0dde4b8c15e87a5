"""The LM tier on a tensor-parallel mesh: ContinuousEngine over one host's chips.

As :mod:`bench.systems.lm`, whose window, metrics and check this module
reuses, with three differences in set-up: the configuration's ``mesh``
(``data`` × ``model``) is built over the cell's devices (a rehearsal
splits the model over the CPU devices it has: one gives a 1×1 mesh);
the weights are made from the seed already split as the program's
logical specs place them, so the whole model never sits on one chip;
and the engine is given the mesh and those specs.  The window also
records ``ContinuousStats.place_s``, the host time spent putting each
step's inputs on the mesh.
"""

from __future__ import annotations

import time
from typing import Dict

from bench import loadgen, workload
from bench.systems import lm
from bench.systems.lm import (  # noqa: F401 — run.py calls them by name
    State, attempted_failed, check, control, end_to_end, requests_in_window,
    trace_mark, traced_prompts,
)

__all__ = ["setup", "trace_mark", "measure", "end_to_end", "attempted_failed",
           "requests_in_window", "traced_prompts", "check", "control"]


def mesh_of(ctx):
    """The configuration's ``(data, model)`` mesh over the cell's devices."""
    from repro.launch.mesh import make_mesh

    want = ctx.config["mesh"]
    shape = (1, len(ctx.devices)) if ctx.rehearse else (want["data"], want["model"])
    return make_mesh(shape, ("data", "model"), devices=ctx.devices)


def setup(ctx) -> State:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ModelConfig
    from repro.launch.sharding import abstract, shardings_from_specs
    from repro.models.registry import build_model
    from repro.serve.engine import ServeConfig
    from repro.serve.kvcache import PagedCacheSpec
    from repro.serve.scheduler import ContinuousEngine

    conf, kind = ctx.config, ctx.kind
    model = conf["model"]
    cfg = ModelConfig(**model)
    mesh = mesh_of(ctx)
    shapes, specs = abstract(build_model(cfg).init,
                             jax.ShapeDtypeStruct((2,), jnp.uint32))
    timings: Dict[str, float] = {}
    t = time.perf_counter()
    params = ctx.reference.init_params(
        model, ctx.seed, shardings_from_specs(mesh, specs, shapes))
    jax.block_until_ready(params)
    timings["weights_s"] = time.perf_counter() - t

    paged = conf["paged"]
    m = paged["max_len"] // paged["block_size"]
    spec = PagedCacheSpec(n_blocks=paged["slots"] * m + 1,
                          block_size=paged["block_size"],
                          max_slots=paged["slots"], max_blocks_per_seq=m)
    engine = ContinuousEngine(cfg, params, spec,
                              ServeConfig(max_len=paged["max_len"], greedy=True),
                              mesh=mesh, param_specs=specs)
    streams = workload.streams(kind, ctx.traffic, {}, ctx.seed)
    texts = {}
    for s in streams:
        for r in s:
            prompt_tokens, _, text_seed = kind.request(r)
            texts[text_seed] = kind.text(prompt_tokens - 1, text_seed)
    state = State(conf, kind, model, params, engine, streams, texts,
                  timings=timings)

    # warm, as lm does: one request per prefill bucket of the request list,
    # each through prefill, the paged write and one decode step
    t = time.perf_counter()
    bs = paged["block_size"]
    buckets = sorted({-(-kind.request(r)[0] // bs) * bs for s in streams for r in s})
    for b in buckets:
        engine.generate([kind.text(b - 1, b)], max_new_tokens=2)
    timings["warm_buckets"] = len(buckets)
    timings["warm_s"] = time.perf_counter() - t
    return lm._start_server(state)


def measure(ctx, state: State, on_start=None) -> loadgen.Window:
    """:func:`bench.systems.lm.measure`, with ``place_s`` at the edges."""
    st = state.engine.stats

    def counters():
        return {"decode_tokens": st.decode_tokens, "steps": st.steps,
                "place_s": st.place_s}

    def opened(t):
        state.counters["before"] = counters()
        if on_start is not None:
            on_start(t)

    def closed(t):
        state.counters["after"] = counters()

    state.window = loadgen.closed_loop(
        state.kind.call(state), state.streams, ctx.seconds, span=state.kind.span,
        on_start=opened, on_stop=closed, ramp_s=state.conf["ramp_seconds"],
    )
    state.stop.set()
    state.server.join(timeout=60)
    state.timings["ramp_s"] = state.window.ramp_s
    return state.window
