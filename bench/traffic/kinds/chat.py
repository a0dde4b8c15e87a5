"""Chat requests through ``ContinuousEngine.submit``.

Each request is ``(prompt_tokens, output_tokens, text_seed)``.  The mix's
``requests`` sizes are fixed mid-quantiles of two clipped lognormals,
shuffled by the seed and dealt round-robin to the clients; the prompt
text is ``prompt_tokens - 1`` printable characters from ``text_seed``
(the program's byte tokenizer adds BOS).  Greedy, so the check can
compare every served token with the reference's best.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from bench.workload import lognormal_set

span = "bench.chat"


@dataclass
class Answer:
    tokens: List[int]
    t_first: float


def streams(p: dict, sizes: Dict[str, int], rng) -> List[np.ndarray]:
    n = p["requests"]
    prompt = lognormal_set(n, **p["prompt_tokens"])
    output = lognormal_set(n, **p["output_tokens"])
    prompt = prompt[rng.permutation(n)]
    output = output[rng.permutation(n)]
    text = rng.integers(0, 2**31, n)
    req = np.stack([prompt, output, text], axis=1)
    return [req[c::p["clients"]] for c in range(p["clients"])]


def request(req) -> tuple:
    """``(prompt_tokens, output_tokens, text_seed)`` as ints."""
    prompt_tokens, out_tokens, text_seed = (int(x) for x in req)
    return prompt_tokens, out_tokens, text_seed


def text(length: int, seed: int) -> str:
    """``length`` printable ASCII characters from ``seed``."""
    rng = np.random.default_rng(int(seed))
    return rng.integers(33, 127, length, dtype=np.uint8).tobytes().decode()


def call(state):
    """Submit one request and wait for all of it.  The engine streams
    nothing, so the client takes its first token's time as its own
    completion time less the decode time the engine reports with the
    result (``GenerationResult.decode_s``: from the first token on the
    host to the last); ``tests/bench`` pins that meaning."""
    engine = state.engine

    def chat(req):
        _, out_tokens, text_seed = request(req)
        done = {}
        fut = engine.submit(state.texts[text_seed], max_new_tokens=out_tokens,
                            lead=False)
        fut.add_done_callback(lambda f: done.setdefault("t", time.perf_counter()))
        res = fut.result()
        t_done = done.get("t", time.perf_counter())
        return Answer(list(res.token_ids), t_done - res.decode_s)

    return chat
