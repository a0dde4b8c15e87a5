"""Public jit'd entry point for ``sorted_probe`` (stages A + B + C).

``sorted_probe(queries, table)`` — membership of (Q,2) uint32 keys in a
sorted unique (M,2) uint32 table.  Dispatches stage B to the Pallas kernel
on TPU (or when forced), otherwise runs the pure-jnp reference.

Exactness guarantee: bucket overflow (more than QMAX queries routed to one
table block — possible only under adversarial key clustering; digests are
uniform) is detected and those queries are resolved through the reference
binary search, so results are always exact.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.device import on_tpu

from .kernel import DEFAULT_TABLE_BLOCK, SENTINEL, probe_blocks_pallas
from .ref import pair_eq, pair_less, sort_pairs, sorted_probe_ref

__all__ = ["sorted_probe", "sorted_probe_pallas"]


def _fence_assign(sorted_q: jax.Array, fences: jax.Array) -> jax.Array:
    """Block id per query: rightmost fence <= q (branch-free bin search)."""
    nb = fences.shape[0]
    q_hi, q_lo = sorted_q[:, 0], sorted_q[:, 1]
    f_hi, f_lo = fences[:, 0], fences[:, 1]
    lo_b = jnp.zeros((sorted_q.shape[0],), jnp.int32)
    hi_b = jnp.full((sorted_q.shape[0],), nb, jnp.int32)
    # fixed-step search with convergence guard (see ref.sorted_probe_ref)
    steps = max(1, nb.bit_length())
    for _ in range(steps):
        active = lo_b < hi_b
        mid = (lo_b + hi_b) // 2
        mh = jnp.take(f_hi, mid, mode="clip")
        ml = jnp.take(f_lo, mid, mode="clip")
        le = ~pair_less(q_hi, q_lo, mh, ml)  # fence[mid] <= q
        lo_b = jnp.where(active & le, mid + 1, lo_b)
        hi_b = jnp.where(active & ~le, mid, hi_b)
    return jnp.maximum(lo_b - 1, 0)  # rightmost fence <= q (clamped)


@functools.partial(
    jax.jit, static_argnames=("table_block", "qmax", "interpret")
)
def sorted_probe_pallas(
    queries: jax.Array,
    table: jax.Array,
    table_block: int = DEFAULT_TABLE_BLOCK,
    qmax: int | None = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fence-partitioned Pallas probe; exact (overflow falls back to ref)."""
    q_n = queries.shape[0]
    m = table.shape[0]
    if m == 0 or q_n == 0:
        return jnp.zeros((q_n,), bool), jnp.zeros((q_n,), jnp.int32)

    bt = min(table_block, max(128, m))
    nblocks = (m + bt - 1) // bt
    m_pad = nblocks * bt
    pad = jnp.full((m_pad - m, 2), SENTINEL, jnp.uint32)
    t_pad = jnp.concatenate([table, pad], axis=0) if m_pad != m else table
    fences = t_pad[::bt]  # (nblocks, 2)

    # --- stage A: sort queries, assign blocks, bucket ----------------------
    sorted_q, order = sort_pairs(queries)
    bid = _fence_assign(sorted_q, fences)  # (Q,) block per sorted query
    # rank within block: queries sorted => equal bids contiguous
    first = jnp.searchsorted(bid, jnp.arange(nblocks, dtype=bid.dtype))
    rank = jnp.arange(q_n, dtype=jnp.int32) - jnp.take(first, bid).astype(jnp.int32)
    if qmax is None:
        avg = (q_n + nblocks - 1) // nblocks
        qmax = max(64, min(q_n, 4 * avg))
        qmax = (qmax + 7) // 8 * 8
    overflow = rank >= qmax
    # overflow queries scatter into a discard slot (index qmax) so they can
    # never clobber a legitimate bucket entry
    rank_c = jnp.minimum(rank, qmax)
    buckets = jnp.full((nblocks, qmax + 1, 2), SENTINEL, jnp.uint32)
    buckets = buckets.at[bid, rank_c].set(sorted_q)[:, :qmax]
    buckets = jnp.swapaxes(buckets, 1, 2)  # (nblocks, 2, qmax): keys on lanes

    # --- stage B: Pallas blocked probe -------------------------------------
    found_b, pos_b = probe_blocks_pallas(
        t_pad.T, buckets, table_block=bt, interpret=interpret
    )

    # --- stage C: gather back + overflow fallback --------------------------
    found_s = found_b[bid, rank_c].astype(bool)
    pos_s = pos_b[bid, rank_c]
    any_ovf = jnp.any(overflow)

    def _with_fallback():
        f_ref, p_ref = sorted_probe_ref(sorted_q, table)
        return (
            jnp.where(overflow, f_ref, found_s),
            jnp.where(overflow, p_ref, pos_s),
        )

    def _no_fallback():
        return found_s, pos_s

    found_s, pos_s = jax.lax.cond(any_ovf, _with_fallback, _no_fallback)
    # mask sentinel-padding hits beyond the real table
    found_s = found_s & (pos_s < m)
    # unsort
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(q_n, dtype=order.dtype))
    return found_s[inv], pos_s[inv]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def sorted_probe(
    queries: jax.Array,
    table: jax.Array,
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Membership probe; kernel on TPU, pure-jnp reference elsewhere."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if use_pallas:
        return sorted_probe_pallas(queries, table, interpret=interpret)
    return sorted_probe_ref(queries, table)
