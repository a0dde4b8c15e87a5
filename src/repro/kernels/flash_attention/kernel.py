"""Pallas TPU flash attention (causal / sliding-window, GQA).

Online-softmax blocked attention:

  grid = (B*Hq, Sq/BQ, Skv/BK)   — kv block index innermost (sequential);
  VMEM blocks: q (BQ, D), k (BK, D), v (BK, D), out (BQ, D);
  f32 scratch carried across kv steps: acc (BQ, D), m (BQ,), l (BQ,).

MXU alignment: BQ, BK multiples of 128; D is the head dim (128/256-class).
VMEM per step (BQ=BK=512, D=128, bf16 in / f32 scratch):
  q/k/v/out ≈ 4 × 512×128×2 B = 512 KiB, scratch ≈ 512×128×4 + 2×512×4
  ≈ 260 KiB  « 16 MiB ✓

Any lengths: the wrapper pads Skv up to whole key blocks (padded key rows
are masked, ``k_pos < skv``) and places the queries on a grid of query
blocks anchored at absolute position 0 — front-padding ``(Skv - Sq) %
BQ`` rows and back-padding to a whole block, all sliced off afterwards.
Block sizes depend on Skv only, so a query row lands in the same block
row, with the same block shapes and the same kv-block schedule, whether
it comes from a full prefill (Sq == Skv) or from a suffix prefill
(Sq < Skv).  That keeps the suffix bit-identical to the matching rows of
the full prefill on any backend, whose matmuls may round differently
for different tile shapes.

Fully-masked kv blocks (beyond the causal frontier or the sliding window)
are skipped with ``pl.when`` — with a window the skip fraction approaches
1 - window/Skv, which is where the kernel's sub-quadratic win comes from.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _fa_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, window: Optional[int],
    bq: int, bk: int, q_start: int, skv: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions (row 0 of the padded queries sits at q_start)
    q_lo = qi * bq + q_start      # first query abs position in this block
    q_hi = q_lo + bq - 1
    k_lo = ki * bk

    # block-level visibility: any (q, k) pair in this tile unmasked?
    visible = True
    if causal:
        visible = jnp.logical_and(visible, k_lo <= q_hi)
    if window is not None:
        visible = jnp.logical_and(visible, k_lo + bk - 1 > q_lo - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale   # (BQ, D)
        k = k_ref[0].astype(jnp.float32)           # (BK, D)
        v = v_ref[0].astype(jnp.float32)           # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # (BQ, BK)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < skv                          # padded key rows
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None]) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,   # (B, Hq, Sq, D)
    k: jax.Array,   # (B, Hkv, Skv, D)
    v: jax.Array,   # (B, Hkv, Skv, D)
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    # blocks never exceed the (sublane-rounded) key sequence; queries are
    # the last sq positions of it, aligned to absolute query blocks
    bq = min(block_q, _round_up(skv, 8))
    bk = min(block_k, _round_up(skv, 8))
    lead = (skv - sq) % bq
    sq_pad = _round_up(lead + sq, bq)
    skv_pad = _round_up(skv, bk)
    g = hq // hkv

    qf = _pad_seq(q.reshape(b * hq, sq, d), lead, sq_pad)
    kf = _pad_seq(k.reshape(b * hkv, skv, d), 0, skv_pad)
    vf = _pad_seq(v.reshape(b * hkv, skv, d), 0, skv_pad)
    grid = (b * hq, sq_pad // bq, skv_pad // bk)

    def kv_index(bh, qi, ki):
        # map flattened q-head index -> flattened kv-head index (GQA)
        return ((bh // hq) * hkv + (bh % hq) // g, ki, 0)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, q_start=skv - sq - lead, skv=skv,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_pad, d), q.dtype),
        scratch_shapes=[
            _vmem((bq, d)),   # acc
            _vmem((bq,)),     # m (running max)
            _vmem((bq,)),     # l (running denom)
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, lead:lead + sq].reshape(b, hq, sq, d)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad_seq(x: jax.Array, front: int, length: int) -> jax.Array:
    """Zero-pad axis 1 of a (BH, S, D) array: ``front`` rows before, then
    up to ``length`` rows in all."""
    back = length - front - x.shape[1]
    if not front and not back:
        return x
    return jnp.pad(x, ((0, 0), (front, back), (0, 0)))


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)
