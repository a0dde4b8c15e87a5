"""Pallas TPU kernel for ``sorted_probe``: fence-partitioned membership.

The TPU adaptation of the paper's index lookup: a CPU hash map is
pointer-chasing and does not vectorize; a *sorted dense table* +
*fence-partitioned broadcast compare* does:

  stage A (jnp, ops.py) — sort queries, assign each to a table block via a
    fence search (fence = every B_T-th table key), bucket queries per block;
  stage B (this kernel)  — grid over table blocks: each step holds one
    ``(2, B_T)`` table block (hi and lo planes, keys across the lanes) and
    its ``(2, QMAX)`` query bucket (keys across the lanes) in VMEM, turns
    the table block into ``(B_T, 2)`` columns in-register, and resolves
    membership with a dense ``(B_T × QMAX)`` lexicographic compare of a
    column against a row (VPU-regular, branch-free — the TPU-idiomatic
    substitute for per-query binary search, whose dynamic lane gathers
    are the expensive thing on this hardware);
  stage C (jnp, ops.py) — scatter results back to original query order.

Every block's last two dims are either the full array dims or (8, 128)
aligned, which the Mosaic lowering requires: per-block outputs are
``(nblocks, 1, QMAX)`` rows, not ``(1, QMAX)`` slices of a 2-D array.
Neither operand is lane-padded in HBM: a ``(M, 2)`` table laid out as
tiles would take 64x its size (539 MB of temporaries at M = 2**20,
compiled for v5e).

VMEM per grid step (B_T=2048, QMAX=512):
  table 8(sublane-padded)×2048×4 B = 64 KiB, queries 8×512×4 B = 16 KiB,
  compare matrices 2×2048×512 int32 = 8 MiB  < 16 MiB scoped ✓
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["probe_blocks_pallas", "DEFAULT_TABLE_BLOCK", "SENTINEL"]

DEFAULT_TABLE_BLOCK = 2048
SENTINEL = 0xFFFFFFFF  # bucket padding key (never a valid query by masking)


def _probe_kernel(t_ref, q_ref, found_ref, pos_ref, *, table_block: int):
    t = t_ref[...].T  # (2, B_T) planes -> (B_T, 2) uint32, sorted ascending
    q = q_ref[0]     # (2, QMAX) uint32 bucket (sentinel-padded)
    t_hi, t_lo = t[:, 0:1], t[:, 1:2]   # (B_T, 1) columns
    q_hi, q_lo = q[0:1, :], q[1:2, :]   # (1, QMAX) rows
    # dense lexicographic compare: (B_T, QMAX)
    lt = (t_hi < q_hi) | ((t_hi == q_hi) & (t_lo < q_lo))
    eq = (t_hi == q_hi) & (t_lo == q_lo)
    # lower bound within block / membership, reduced down the sublanes
    count = jnp.sum(lt.astype(jnp.int32), axis=0, keepdims=True)
    found = jnp.max(eq.astype(jnp.int32), axis=0, keepdims=True)
    base = pl.program_id(0) * table_block
    found_ref[0] = found
    pos_ref[0] = base + count


def probe_blocks_pallas(
    table_planes: jax.Array,   # (2, nblocks * B_T) uint32 hi/lo, sorted + sentinel pad
    buckets: jax.Array,        # (nblocks, 2, QMAX) uint32 bucketed queries
    table_block: int = DEFAULT_TABLE_BLOCK,
    interpret: bool = False,
):
    """Stage B: per-block membership. Returns (found, pos) of shape
    ``(nblocks, QMAX)``; ``pos`` is the global lower-bound index assuming the
    query was routed to the correct block (stage A's fence invariant)."""
    nblocks, _, qmax = buckets.shape
    if table_planes.shape[1] != nblocks * table_block:
        raise ValueError(
            f"table rows {table_planes.shape[1]} != nblocks*B_T "
            f"{nblocks}*{table_block}"
        )
    kernel = functools.partial(_probe_kernel, table_block=table_block)
    found, pos = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((2, table_block), lambda i: (0, i)),
            pl.BlockSpec((1, 2, qmax), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, qmax), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, qmax), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, 1, qmax), jnp.int32),
            jax.ShapeDtypeStruct((nblocks, 1, qmax), jnp.int32),
        ],
        interpret=interpret,
    )(table_planes, buckets)
    return found[:, 0], pos[:, 0]
