"""The LM tier as deployed: ContinuousEngine over the paged KV cache.

Set-up makes the weights from the seed (the configuration's reference
module, one jitted call on the device), builds ``ContinuousEngine`` with
the configuration's paged pool, and warms every prefill bucket the cell's
request list contains plus the decode step.  One serving thread leads
the engine's decode loop; the clients submit without leading.  The
window is a closed loop after a ramp, so that it opens with the slots in
steady use.

The request call is the traffic kind's (``bench/traffic/kinds/chat.py``);
of the kind this driver also asks ``request(row) -> (prompt_tokens,
output_tokens, text_seed)`` and ``text(length, seed)``.

After the window the engine is freed and a seeded sample of the finished
requests, the longest among them, is run through the float32 reference
over its prompt and served tokens: the number compared is the widest gap
by which a served token's reference logit lies below the reference's best.
"""

from __future__ import annotations

import gc
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from bench import loadgen, workload

__all__ = ["setup", "trace_mark", "measure", "end_to_end", "attempted_failed",
           "requests_in_window", "traced_prompts", "check", "control"]

_BOS = 256  # the program's byte tokenizer: bytes, then BOS, EOS, PAD
_PAD = 258


@dataclass
class State:
    conf: dict
    kind: Any                       # the traffic kind's module
    model: dict
    params: Any
    engine: Any
    streams: List[np.ndarray]
    texts: Dict[int, str]
    counters: Dict[str, Dict[str, float]] = field(default_factory=dict)
    window: Optional[loadgen.Window] = None
    timings: Dict[str, float] = field(default_factory=dict)
    stop: threading.Event = field(default_factory=threading.Event)
    server: Optional[threading.Thread] = None
    chosen: list = field(default_factory=list)     # the requests compared


def setup(ctx) -> State:
    import jax

    from repro.configs.base import ModelConfig
    from repro.serve.engine import ServeConfig
    from repro.serve.kvcache import PagedCacheSpec
    from repro.serve.scheduler import ContinuousEngine

    conf, kind = ctx.config, ctx.kind
    model = conf["model"]
    timings: Dict[str, float] = {}
    t = time.perf_counter()
    params = ctx.reference.init_params(model, ctx.seed)
    jax.block_until_ready(params)
    timings["weights_s"] = time.perf_counter() - t

    paged = conf["paged"]
    m = paged["max_len"] // paged["block_size"]
    spec = PagedCacheSpec(n_blocks=paged["slots"] * m + 1,
                          block_size=paged["block_size"],
                          max_slots=paged["slots"], max_blocks_per_seq=m)
    cfg = ModelConfig(**{k: v for k, v in model.items()})
    engine = ContinuousEngine(cfg, params, spec,
                              ServeConfig(max_len=paged["max_len"], greedy=True))
    streams = workload.streams(kind, ctx.traffic, {}, ctx.seed)
    texts = {}
    for s in streams:
        for r in s:
            prompt_tokens, _, text_seed = kind.request(r)
            texts[text_seed] = kind.text(prompt_tokens - 1, text_seed)
    state = State(conf, kind, model, params, engine, streams, texts,
                  timings=timings)

    # warm: one request per prefill bucket the request list holds (its
    # prompt padded to the block size), each through prefill, the paged
    # write and one decode step
    t = time.perf_counter()
    bs = paged["block_size"]
    buckets = sorted({-(-kind.request(r)[0] // bs) * bs for s in streams for r in s})
    for b in buckets:
        engine.generate([kind.text(b - 1, b)], max_new_tokens=2)
    timings["warm_buckets"] = len(buckets)
    timings["warm_s"] = time.perf_counter() - t
    return _start_server(state)


def _start_server(state: State) -> State:
    engine = state.engine

    def serve() -> None:
        # the serving thread: leads the decode loop whenever work is queued
        while not state.stop.is_set():
            engine.generate([])
            time.sleep(0.0002)

    state.server = threading.Thread(target=serve, name="bench-serve", daemon=True)
    state.server.start()
    return state


def trace_mark(ctx, state: State, which: str) -> None:
    """Engine counters at the traced window's edges (decode tokens)."""
    st = state.engine.stats
    state.counters[f"trace_{which}"] = {
        "decode_tokens": st.decode_tokens, "steps": st.steps,
        "t": time.perf_counter(),
    }


def measure(ctx, state: State, on_start=None) -> loadgen.Window:
    st = state.engine.stats

    def opened(t):
        state.counters["before"] = {"decode_tokens": st.decode_tokens,
                                    "steps": st.steps}
        if on_start is not None:
            on_start(t)

    def closed(t):
        state.counters["after"] = {"decode_tokens": st.decode_tokens,
                                   "steps": st.steps}

    state.window = loadgen.closed_loop(
        state.kind.call(state), state.streams, ctx.seconds, span=state.kind.span,
        on_start=opened, on_stop=closed, ramp_s=state.conf["ramp_seconds"],
    )
    state.stop.set()
    state.server.join(timeout=60)
    state.timings["ramp_s"] = state.window.ramp_s
    return state.window


def _spec(state: State, r) -> tuple:
    s = state.streams[r.client]
    return state.kind.request(s[r.index % len(s)])


def end_to_end(ctx, state: State) -> Dict[str, float]:
    """``tokens_per_s``: output tokens emitted inside the window over its
    length (a request's decode tokens spread evenly between its first and
    last token); ``ttft_p95_ms``: 95th percentile, over every request whose
    first token came inside the window, of submit to first token."""
    w = state.window
    tokens = 0
    ttft = []
    for r in w.records:
        if r.error is not None:
            if w.t_start <= r.t_send < w.t_end:
                ttft.append(np.inf)
            continue
        a = r.answer
        n = len(a.tokens)
        times = np.array([a.t_first]) if n == 1 else \
            a.t_first + np.arange(n) * (r.t_done - a.t_first) / (n - 1)
        tokens += int(((times >= w.t_start) & (times < w.t_end)).sum())
        if w.t_start <= a.t_first < w.t_end:
            ttft.append((a.t_first - r.t_send) * 1e3)
    return {
        "tokens_per_s": tokens / w.seconds,
        "ttft_p95_ms": float(np.percentile(ttft, 95, method="higher")) if ttft else float("inf"),
    }


def attempted_failed(state: State):
    w = state.window
    sent = [r for r in w.records if w.t_start <= r.t_send < w.t_end]
    return len(sent), sum(r.error is not None for r in sent)


def requests_in_window(state: State) -> int:
    return attempted_failed(state)[0]


def prompt_ids(state: State, r) -> List[int]:
    _, _, text_seed = _spec(state, r)
    return [_BOS] + list(state.texts[text_seed].encode())


def traced_prompts(state: State) -> List[int]:
    """Prompt lengths of the requests whose first token came while traced."""
    c = state.counters
    if "trace_start" not in c or "trace_stop" not in c:
        return []
    lo, hi = c["trace_start"]["t"], c["trace_stop"]["t"]
    return [len(prompt_ids(state, r)) for r in state.window.records
            if r.error is None and lo <= r.answer.t_first < hi]


def sample(ctx, state: State) -> list:
    """The finished requests the check reads: the longest sent in the
    window, then others drawn from the seed."""
    w = state.window
    done = [r for r in w.records
            if r.error is None and w.t_start <= r.t_send < w.t_end]
    if not done:
        return []
    size = lambda r: len(prompt_ids(state, r)) + len(r.answer.tokens)  # noqa: E731
    longest = max(done, key=size)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([ctx.seed, 0xC4EC])
    pick = rng.permutation(len(rest))[: state.conf["check"]["requests"] - 1]
    return [longest] + [rest[i] for i in pick]


def free(state: State) -> None:
    """Close the engine and drop its KV pool; the weights stay."""
    state.engine.close()
    state.engine = None
    gc.collect()


def reference_gaps(ctx, state: State, chosen, fp8_control: bool = False
                   ) -> np.ndarray:
    """Gap of every served token of ``chosen`` (see the module docstring)."""
    import jax
    import jax.numpy as jnp

    model = state.model
    s_max = state.conf["paged"]["max_len"]
    r_max = int(max(state.conf["check"]["rows"], 1))
    fn = jax.jit(lambda p, t, rows, served: ctx.reference.gaps(
        p, model, t, rows, served, fp8_control))
    out = []
    for r in chosen:
        prompt = prompt_ids(state, r)
        served = r.answer.tokens
        seq = np.full(s_max, _PAD, np.int32)
        full = prompt + served[:-1]
        seq[: len(full)] = full
        n = len(served)
        for lo in range(0, n, r_max):
            k = min(r_max, n - lo)
            rows = np.zeros(r_max, np.int32)
            rows[:k] = len(prompt) - 1 + lo + np.arange(k)
            tok = np.zeros(r_max, np.int32)
            tok[:k] = served[lo: lo + k]
            g = np.asarray(fn(state.params, jnp.asarray(seq), jnp.asarray(rows),
                              jnp.asarray(tok)))
            out.append(g[:k])
    return np.concatenate(out) if out else np.zeros(0)


def check(ctx, state: State, reference) -> List[tuple]:
    chosen = sample(ctx, state)
    state.chosen = chosen
    free(state)
    gaps = reference_gaps(ctx, state, chosen)
    state.timings["check_tokens"] = int(len(gaps))
    widest = float(gaps.max()) if len(gaps) else float("inf")
    return [("served_token_logit_gap", widest, state.conf["check"]["gap_limit"])]


def control(ctx, state: State, reference) -> List[tuple]:
    """The gap of the token the float8 control puts first, at the same
    positions of the same prompts and served tokens (after :func:`check`)."""
    gaps = reference_gaps(ctx, state, state.chosen, fp8_control=True)
    widest = float(gaps.max()) if len(gaps) else float("inf")
    return [("served_token_logit_gap", widest, state.conf["check"]["gap_limit"])]
