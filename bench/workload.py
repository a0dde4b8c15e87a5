"""Request streams from a traffic mix, and the draws the kinds share.

A mix is a data file of parameters, ``bench/traffic/<mix>.json``; its
``"kind"`` names the one generator that reads it,
``bench/traffic/kinds/<kind>.py``.  A kind module has

* ``streams(params, sizes, rng) -> list``: one request stream per
  closed-loop client, from the mix's parameters, the cell's sizes and a
  generator seeded from the run's seed;
* ``span``: the name of the profiler span around each request;
* ``call(state) -> callable``: the function a client calls with one
  request, on the system driver's state;

and whatever its system driver asks of it besides (warm-up, check,
control).  The same seed gives the same streams.  Where a mix fixes a set
of sizes (the chat mix's prompt and output lengths), every seed gets the
same set in another order, so the seed changes which request comes when
and not how much work there is.
"""

from __future__ import annotations

from types import ModuleType
from typing import Dict, List

import numpy as np

__all__ = ["streams", "zipf_ranks", "lognormal_set"]


def zipf_ranks(rng: np.random.Generator, n_items: int, theta: float,
               size) -> np.ndarray:
    """Ranks in ``[0, n_items)`` with P(rank r) proportional to (r+1)**-theta
    (YCSB's Zipfian; rank 0 is the hottest)."""
    w = np.arange(1, n_items + 1, dtype=np.float64) ** -theta
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, rng.random(size)), n_items - 1)


def lognormal_set(n: int, median: float, sigma: float, lo: int, hi: int
                  ) -> np.ndarray:
    """``n`` sizes at the mid-quantiles of a clipped lognormal: the same set
    for every seed."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(np.int64)


def streams(kind: ModuleType, traffic: dict, sizes: Dict[str, int], seed: int
            ) -> List[np.ndarray]:
    """One request stream per client for the mix ``traffic`` and ``seed``."""
    rng = np.random.default_rng([seed, 0x7F4A])
    return kind.streams(traffic, sizes, rng)
