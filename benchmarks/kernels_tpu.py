"""Device-path throughput: hash_mix digesting and sorted_probe membership.

These are the TPU adaptations of the paper's hot loops, measured on
whichever path :func:`repro.device.on_tpu` selects: the Pallas kernels on
a TPU, the XLA reference path elsewhere.  Derived column reports ids/s
so the number is directly comparable to the paper's host-side rates
(3,243 mol/s naïve scan; ~1e6/s dict lookups).
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.identifiers import canonical_id, molecule_from_cid
from repro.core.packing import pack_ids
from repro.kernels.hash_mix.ops import hash_mix
from repro.kernels.sorted_probe.ops import sorted_probe
from repro.kernels.sorted_probe.ref import sort_pairs

from .common import row, timeit


def run() -> List[str]:
    out = []
    n = 20_000
    ids = [canonical_id(molecule_from_cid(c)) for c in range(n)]
    packed = jnp.asarray(pack_ids(ids))

    d = hash_mix(packed)  # compile
    t, _ = timeit(lambda: hash_mix(packed).block_until_ready(), repeats=3)
    out.append(row("kernels.hash_mix", t, f"{n/t:.0f} ids/s (XLA path)"))

    table = jnp.asarray(np.asarray(d[:, :2]))
    table_sorted, _ = sort_pairs(table)
    queries = table[: n // 2]
    f, p = sorted_probe(queries, table_sorted)  # compile
    t, _ = timeit(
        lambda: sorted_probe(queries, table_sorted)[0].block_until_ready(),
        repeats=3,
    )
    out.append(row(
        "kernels.sorted_probe", t,
        f"{queries.shape[0]/t:.0f} lookups/s over {n}-entry table "
        f"(paper dict: ~1.2 µs/lookup)",
    ))
    return out
