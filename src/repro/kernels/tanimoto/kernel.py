"""Pallas TPU kernel for batched Tanimoto top-k over packed fingerprints.

Similarity screening is the first workload in this repo where the Pallas
kernel is the *throughput* lever rather than a probe: every query must
touch every database row (no digest routing to hide behind), so the job
is a dense streaming scan — exactly what the VPU's 8x128 lanes want.

Layout (mirrors ``sorted_probe``'s staged shape):

  grid over database blocks: step ``i`` holds one ``(W, B_D)`` uint32
  fingerprint block — transposed, so word ``w`` of every row in the
  block is one lane-dense ``(1, B_D)`` row — plus its ``(1, B_D)``
  precomputed popcounts in VMEM, with the full ``(Q, W)`` query plane
  and its ``(Q, 1)`` popcounts resident across steps;

  per step — intersection popcounts via a SWAR bit-trick popcount over
  uint32 words (branch-free adds/shifts/masks, no lookup tables to
  gather through), one ``(Q, B_D)`` lane matrix per word (a query
  column broadcast against a database row), statically unrolled over
  the ``W`` words; union from the precomputed row popcounts
  (``|q| + |d| - c``); score ``c / u`` in float32, correctly rounded
  (:func:`round_quotient`) since the chip's divide is not;

  a running per-query top-k lives in the *output* refs (constant index
  map → the block stays in VMEM across all grid steps), padded to whole
  128-lane tiles so the merge concatenates lane-aligned tiles: each
  step merges its ``(Q, B_D)`` candidate scores into the running heap
  by K rounds of masked max-extraction — first-occurrence ties, which
  (run entries sorted, block indices ascending, run indices always below
  the current block's) is exactly the oracle's ``(score desc, index
  asc)`` order.

Every block's last two dims are full array dims or (8, 128) aligned, as
the Mosaic lowering requires.

VMEM per grid step (Q=256, B_D=256, W=32, K=32):
  queries 256x128(lane-padded)x4 B = 128 KiB, block 32 KiB,
  score/intersection matrices ~4x256x384x4 B = 1.5 MiB, running top-k
  2x256x128x4 B = 256 KiB  « 16 MiB ✓
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.fingerprint import MAX_FP_BITS as MAX_BITS

__all__ = [
    "tanimoto_blocks_pallas",
    "round_quotient",
    "DEFAULT_DB_BLOCK",
    "MAX_BITS",
    "PAD_IDX_SENTINEL",
]

DEFAULT_DB_BLOCK = 256
# the running top-k is padded to a multiple of this many lanes
HEAP_LANES = 128
# running-heap slots start at this index with score -1; any real row
# (score >= 0) displaces them, and survivors are mapped to -1 on the host
PAD_IDX_SENTINEL = 2**31 - 1

_M1 = np.uint32(0x55555555)
_M2 = np.uint32(0x33333333)
_M4 = np.uint32(0x0F0F0F0F)


def _popcount_u32(x: jax.Array) -> jax.Array:
    """SWAR popcount of a uint32 array (exact, branch-free, no gathers)."""
    x = x - ((x >> np.uint32(1)) & _M1)
    x = (x & _M2) + ((x >> np.uint32(2)) & _M2)
    x = (x + (x >> np.uint32(4))) & _M4
    x = x + (x >> np.uint32(8))
    x = (x + (x >> np.uint32(16))) & np.uint32(0x3F)
    return x.astype(jnp.int32)


def _residual(c: jax.Array, u: jax.Array, q: jax.Array) -> jax.Array:
    """Exact ``c - q * u`` for integer-valued float32 ``0 <= c <= u <= 2**12``.

    ``q`` splits into two 12-bit halves, so both products are exact; the
    first difference is exact by Sterbenz's lemma (``qh * u`` is within a
    factor of two of ``c``) and the second because its exact value, a
    small multiple of ``q``'s ulp, is representable.
    """
    qh = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(q, jnp.uint32) & np.uint32(0xFFFFF000),
        jnp.float32,
    )
    return (c - qh * u) - (q - qh) * u


def _step(q: jax.Array, by: int) -> jax.Array:
    """``q`` moved ``by`` ulps (positive, finite ``q``)."""
    bits = jax.lax.bitcast_convert_type(q, jnp.int32) + by
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def round_quotient(c: jax.Array, u: jax.Array, q0: jax.Array) -> jax.Array:
    """The correctly rounded float32 ``c / u`` from an estimate ``q0``.

    The TPU's float32 divide is accurate to a few ulps, not correctly
    rounded, and the scores' contract (``ref.py``) is the IEEE quotient,
    whose order the top-k ties follow.  One Newton step on the exact
    residual brings ``q0`` within an ulp; of it and its two neighbours,
    the one with the smallest residual is the rounded quotient (a
    quotient of integers below 2**12 is never halfway between two
    floats).  Takes integer-valued float32 ``0 < c <= u <= 2**12``.
    """
    q = q0 + _residual(c, u, q0) / u
    best = q
    best_r = jnp.abs(_residual(c, u, q))
    for cand in (_step(q, -1), _step(q, 1)):
        r = jnp.abs(_residual(c, u, cand))
        best = jnp.where(r < best_r, cand, best)
        best_r = jnp.minimum(r, best_r)
    return best


def _tanimoto_kernel(
    db_ref,      # (W, B_D) uint32 — this step's database block, transposed
    dbc_ref,     # (1, B_D) int32  — its precomputed row popcounts
    q_ref,       # (Q, W) uint32   — the full query plane (every step)
    qc_ref,      # (Q, 1) int32    — query popcounts
    scores_ref,  # (Q, H) f32      — running top-k scores (accumulator)
    idx_ref,     # (Q, H) int32    — running top-k global row indices
    *,
    block_d: int,
    k_pad: int,
    n_db: int,
    n_words: int,
):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        scores_ref[...] = jnp.full(scores_ref.shape, -1.0, jnp.float32)
        idx_ref[...] = jnp.full(idx_ref.shape, PAD_IDX_SENTINEL, jnp.int32)

    d = db_ref[...]
    q = q_ref[...]
    qn = q.shape[0]

    # intersection popcount, one (Q, B_D) lane matrix per word (static
    # unroll — W is a compile-time constant, no dynamic lane slicing)
    inter = jnp.zeros((qn, block_d), jnp.int32)
    for w in range(n_words):
        inter += _popcount_u32(q[:, w:w + 1] & d[w:w + 1, :])
    union = qc_ref[...] + dbc_ref[...] - inter
    c = inter.astype(jnp.float32)
    u = jnp.maximum(union, 1).astype(jnp.float32)
    score = jnp.where(inter > 0, round_quotient(c, u, c / u), 0.0)
    rows = step * block_d + jax.lax.broadcasted_iota(
        jnp.int32, (qn, block_d), 1
    )
    valid = rows < n_db  # sentinel-padded tail rows never place
    score = jnp.where(valid, score, -1.0)
    rows = jnp.where(valid, rows, PAD_IDX_SENTINEL)

    # merge into the running top-k: K rounds of masked max-extraction.
    # First-occurrence tie-break == (score desc, index asc): running
    # entries (always from earlier blocks, i.e. smaller indices) come
    # first in the concat, and both halves are ascending-index within
    # equal scores.  Heap lanes past k_pad stay (-1, sentinel).
    all_s = jnp.concatenate([scores_ref[...], score], axis=1)
    all_i = jnp.concatenate([idx_ref[...], rows], axis=1)
    cols = jax.lax.broadcasted_iota(jnp.int32, all_s.shape, 1)
    heap_col = jax.lax.broadcasted_iota(jnp.int32, scores_ref.shape, 1)
    top_s = jnp.full(scores_ref.shape, -1.0, jnp.float32)
    top_i = jnp.full(idx_ref.shape, PAD_IDX_SENTINEL, jnp.int32)
    for r in range(k_pad):
        m = jnp.max(all_s, axis=1, keepdims=True)
        at_max = all_s == m
        first = jnp.min(
            jnp.where(at_max, cols, PAD_IDX_SENTINEL), axis=1, keepdims=True
        )
        sel = cols == first
        pick = jnp.sum(jnp.where(sel, all_i, 0), axis=1, keepdims=True)
        top_s = jnp.where(heap_col == r, m, top_s)
        top_i = jnp.where(heap_col == r, pick, top_i)
        all_s = jnp.where(sel, -2.0, all_s)  # below any pad: never re-picked
    scores_ref[...] = top_s
    idx_ref[...] = top_i


@functools.partial(
    jax.jit, static_argnames=("block_d", "k_pad", "n_db", "interpret")
)
def tanimoto_blocks_pallas(
    db_t: jax.Array,        # (W, nblocks * B_D) uint32, zero-padded tail
    dbc: jax.Array,         # (1, nblocks * B_D) int32 row popcounts
    queries: jax.Array,     # (Q, W) uint32
    q_counts: jax.Array,    # (Q, 1) int32
    block_d: int = DEFAULT_DB_BLOCK,
    k_pad: int = 8,
    n_db: int = 0,
    interpret: bool = False,
):
    """Streamed top-k: returns ``(scores (Q, k_pad) f32, idx (Q, k_pad) i32)``.

    ``idx`` holds global database row indices; slots that never filled
    (fewer than ``k_pad`` real rows) carry ``score -1`` and the pad
    sentinel index — the ops wrapper maps them to the oracle's ``-1``.
    """
    n_words, d_pad = db_t.shape
    nblocks = d_pad // block_d
    if d_pad != nblocks * block_d or nblocks == 0:
        raise ValueError(
            f"database rows {d_pad} not a positive multiple "
            f"of block_d {block_d}"
        )
    if n_words * 32 > MAX_BITS:
        raise ValueError(
            f"{n_words * 32}-bit fingerprints: the kernel's exact scores "
            f"take at most {MAX_BITS} bits"
        )
    if k_pad < 1:
        raise ValueError(f"k_pad must be >= 1, got {k_pad}")
    heap = -(-k_pad // HEAP_LANES) * HEAP_LANES
    qn = queries.shape[0]
    kernel = functools.partial(
        _tanimoto_kernel,
        block_d=block_d,
        k_pad=k_pad,
        n_db=n_db,
        n_words=n_words,
    )
    scores, idx = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((n_words, block_d), lambda i: (0, i)),
            pl.BlockSpec((1, block_d), lambda i: (0, i)),
            pl.BlockSpec((qn, n_words), lambda i: (0, 0)),
            pl.BlockSpec((qn, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((qn, heap), lambda i: (0, 0)),
            pl.BlockSpec((qn, heap), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qn, heap), jnp.float32),
            jax.ShapeDtypeStruct((qn, heap), jnp.int32),
        ],
        interpret=interpret,
    )(db_t, dbc, queries, q_counts)
    return scores[:, :k_pad], idx[:, :k_pad]
