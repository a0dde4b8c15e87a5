"""Public entry point for batched Tanimoto top-k.

``tanimoto_topk(q_fps, db_fps, k)`` — host numpy in, host numpy out
(the fingerprint planes live in mmap'd sidecars and the results feed
straight into byte-offset column gathers, so unlike ``sorted_probe``
the natural boundary here is numpy, not jax arrays).  Dispatches to the
Pallas kernel on TPU (or when forced / interpreted), otherwise to the
cache-blocked host backend — every backend produces byte-identical
``(scores, indices)`` under the contract documented in ``ref.py``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro.device import on_tpu

from .kernel import DEFAULT_DB_BLOCK, tanimoto_blocks_pallas
from .ref import (
    PAD_INDEX,
    PAD_SCORE,
    _check_plane,
    _merge_running,
    tanimoto_topk_ref,
)

__all__ = ["tanimoto_topk", "tanimoto_topk_host", "tanimoto_topk_pallas"]

# database rows per inner scoring tile on the host path: the (Q, tile)
# uint64/int32 working set stays L2-resident instead of streaming a
# (Q, N) intermediate through main memory per fingerprint word
_HOST_TILE = 1024
# rows per outer top-k merge block (bounds peak memory to (Q, chunk) f32
# at million-row shards, same role as the reference's _DB_CHUNK)
_HOST_CHUNK = 65_536


def _chunk_topk(blk: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Exact ``(score desc, column asc)`` top-k of one ``(Q, M)`` block.

    ``argpartition`` (introselect, O(M)) finds the k-th score per row;
    the reference's full stable mergesort over the block is
    data-dependent and several times slower on realistic score
    distributions.  Partitioning alone breaks boundary ties arbitrarily,
    so the selection is completed exactly: every column strictly above
    the threshold is in, and the remaining slots fill with the *lowest*
    columns at the threshold — the same first-seen-winner order the
    oracle's stable sort produces.
    """
    qn, m = blk.shape
    if m <= k:
        order = np.argsort(-blk, axis=1, kind="stable")
        return (
            np.take_along_axis(blk, order, axis=1),
            order.astype(np.int32),
        )
    part = np.argpartition(-blk, k - 1, axis=1)[:, :k]
    thr = np.take_along_axis(blk, part, axis=1).min(axis=1)
    out_s = np.empty((qn, k), dtype=np.float32)
    out_i = np.empty((qn, k), dtype=np.int32)
    for r in range(qn):
        row = blk[r]
        above = np.nonzero(row > thr[r])[0]
        at = np.nonzero(row == thr[r])[0][: k - above.size]
        cols = np.concatenate([above, at]).astype(np.int32)
        scores = row[cols]
        # k elements: the stable sort keeps ascending columns per score
        order = np.argsort(-scores, kind="stable")
        out_s[r] = scores[order]
        out_i[r] = cols[order]
    return out_s, out_i


def tanimoto_topk_host(
    q_fps: np.ndarray,
    db_fps: np.ndarray,
    k: int,
    q_counts: Optional[np.ndarray] = None,
    db_counts: Optional[np.ndarray] = None,
    db_chunk: int = _HOST_CHUNK,
    tile: int = _HOST_TILE,
) -> Tuple[np.ndarray, np.ndarray]:
    """Cache-blocked host backend; byte-identical to ``tanimoto_topk_ref``.

    Same streaming merge as the reference, but each chunk's score matrix
    comes from an L2-tiled scorer: fingerprint words are viewed two at a
    time as uint64 (halving the word loop), each ``(Q, tile)`` popcount
    accumulation reuses preallocated buffers instead of allocating per
    word, and the float32 division lands tile-wise into the chunk block.
    Chunk top-k selection goes through :func:`_chunk_topk` (partition +
    exact tie completion) instead of the oracle's full stable sort.  The
    intersection counts are the same int32 values, the division is the
    same float32-cast-then-divide, and the tie discipline is the same
    ``(score desc, row asc)``, so results agree with the reference
    byte-for-byte.
    """
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    q_fps = _check_plane(q_fps, "q_fps")
    db_fps = _check_plane(db_fps, "db_fps")
    if q_fps.shape[1] != db_fps.shape[1]:
        raise ValueError(
            f"word width mismatch: queries {q_fps.shape[1]} vs "
            f"database {db_fps.shape[1]}"
        )
    qn, n_words = q_fps.shape
    n_db = db_fps.shape[0]
    if qn == 0 or n_db == 0:
        return (
            np.full((qn, k), PAD_SCORE, dtype=np.float32),
            np.full((qn, k), PAD_INDEX, dtype=np.int32),
        )
    if n_words % 2:
        # 32-bit planes (W odd) have no uint64 view; the chunked
        # reference is already dispatch-bound there anyway
        return tanimoto_topk_ref(
            q_fps, db_fps, k,
            q_counts=q_counts, db_counts=db_counts, db_chunk=db_chunk,
        )
    from repro.core.fingerprint import popcount_u32

    qc = (
        popcount_u32(q_fps).sum(axis=1, dtype=np.int32)
        if q_counts is None else np.asarray(q_counts, dtype=np.int32)
    )
    dc = (
        popcount_u32(db_fps).sum(axis=1, dtype=np.int32)
        if db_counts is None else np.asarray(db_counts, dtype=np.int32)
    )
    q64 = q_fps.view(np.uint64)
    db64 = db_fps.view(np.uint64)
    w64 = q64.shape[1]
    run_s = np.full((qn, k), PAD_SCORE, dtype=np.float32)
    run_i = np.full((qn, k), np.iinfo(np.int32).max, dtype=np.int32)
    anded = np.empty((qn, tile), dtype=np.uint64)
    counts = np.empty((qn, tile), dtype=np.uint8)
    inter = np.empty((qn, tile), dtype=np.int32)
    for lo in range(0, n_db, db_chunk):
        hi = min(lo + db_chunk, n_db)
        blk = np.zeros((qn, hi - lo), dtype=np.float32)
        for tlo in range(lo, hi, tile):
            thi = min(tlo + tile, hi)
            m = thi - tlo
            t = anded[:, :m]
            c = counts[:, :m]
            x = inter[:, :m]
            np.bitwise_and(q64[:, 0, None], db64[None, tlo:thi, 0], out=t)
            np.bitwise_count(t, out=c)
            x[:] = c
            for w in range(1, w64):
                np.bitwise_and(q64[:, w, None], db64[None, tlo:thi, w], out=t)
                np.bitwise_count(t, out=c)
                x += c
            union = qc[:, None] + dc[None, tlo:thi] - x
            np.divide(
                x.astype(np.float32),
                union.astype(np.float32),
                out=blk[:, tlo - lo : thi - lo],
                where=union > 0,
            )
        blk_s, blk_i = _chunk_topk(blk, k)
        run_s, run_i = _merge_running(run_s, run_i, blk_s, blk_i + lo)
    run_i = np.where(run_s < 0.0, PAD_INDEX, run_i)
    run_s = np.where(run_s < 0.0, PAD_SCORE, run_s)
    return run_s, run_i


def _ceil_to(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def tanimoto_topk_pallas(
    q_fps: np.ndarray,
    db_fps: np.ndarray,
    k: int,
    q_counts: Optional[np.ndarray] = None,
    db_counts: Optional[np.ndarray] = None,
    block_d: int = DEFAULT_DB_BLOCK,
    interpret: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """Pad to kernel tiles, run the Pallas scan, strip back to ``(Q, k)``."""
    from repro.core.fingerprint import popcount_u32

    q_fps = np.ascontiguousarray(q_fps, dtype=np.uint32)
    db_fps = np.ascontiguousarray(db_fps, dtype=np.uint32)
    qn, n_words = q_fps.shape
    n_db = db_fps.shape[0]
    if db_fps.shape[1] != n_words:
        raise ValueError(
            f"word width mismatch: queries {n_words} vs database "
            f"{db_fps.shape[1]}"
        )
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if qn == 0 or n_db == 0:
        return (
            np.full((qn, k), PAD_SCORE, dtype=np.float32),
            np.full((qn, k), PAD_INDEX, dtype=np.int32),
        )

    qc = (
        popcount_u32(q_fps).sum(axis=1, dtype=np.int32)
        if q_counts is None else np.asarray(q_counts, dtype=np.int32)
    )
    dc = (
        popcount_u32(db_fps).sum(axis=1, dtype=np.int32)
        if db_counts is None else np.asarray(db_counts, dtype=np.int32)
    )

    # tile the database into lane-aligned blocks of bd rows, transposed to
    # (W, rows) so each fingerprint word is a lane-dense row in the kernel;
    # zero rows (count 0) fill the tail and the kernel masks them via n_db
    bd = min(block_d, _ceil_to(n_db, 128))
    nblocks = -(-n_db // bd)
    d_pad = nblocks * bd
    db_t = np.zeros((n_words, d_pad), dtype=np.uint32)
    db_t[:, :n_db] = db_fps.T
    dc_p = np.zeros((1, d_pad), dtype=np.int32)
    dc_p[0, :n_db] = dc
    # queries pad to a sublane multiple; zero-fp rows are sliced back off
    q_pad = _ceil_to(qn, 8)
    q_p = np.zeros((q_pad, n_words), dtype=np.uint32)
    q_p[:qn] = q_fps
    qc_p = np.zeros((q_pad, 1), dtype=np.int32)
    qc_p[:qn, 0] = qc
    k_pad = _ceil_to(k, 8)

    scores, idx = tanimoto_blocks_pallas(
        db_t,
        dc_p,
        q_p,
        qc_p,
        block_d=bd,
        k_pad=k_pad,
        n_db=n_db,
        interpret=interpret,
    )
    scores = np.asarray(scores)[:qn, :k]
    idx = np.asarray(idx)[:qn, :k]
    # unfilled heap slots carry the in-kernel sentinel; map to the oracle pad
    empty = scores < 0.0
    return (
        np.where(empty, PAD_SCORE, scores).astype(np.float32, copy=False),
        np.where(empty, PAD_INDEX, idx).astype(np.int32, copy=False),
    )


def tanimoto_topk(
    q_fps: np.ndarray,
    db_fps: np.ndarray,
    k: int,
    q_counts: Optional[np.ndarray] = None,
    db_counts: Optional[np.ndarray] = None,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """Batched Tanimoto top-k; kernel on TPU, blocked host path elsewhere.

    ``interpret=True`` forces the Pallas path in interpreter mode (the
    CPU-side parity check); ``use_pallas`` overrides auto-detection.
    """
    if use_pallas is None:
        use_pallas = interpret or on_tpu()
    if use_pallas:
        return tanimoto_topk_pallas(
            q_fps, db_fps, k,
            q_counts=q_counts, db_counts=db_counts, interpret=interpret,
        )
    return tanimoto_topk_host(
        q_fps, db_fps, k, q_counts=q_counts, db_counts=db_counts
    )
