"""Pallas TPU paged decode attention: one query token a slot, its KV read
in place from the block pool.

  grid = (B,)  — one step per slot, in order (DMAs run ahead across steps);
  scalar prefetch: block tables (B*M,), lengths (B,), layer (1,);
  q block (Hkv, G, Dh), output block (Hkv, G, Dh) float32;
  pools stay in HBM (``memory_space=ANY``), shape (L, Hkv, P, Dh).

A slot of length ``n`` is read in ``ceil(n / T)`` chunks of ``T = ppc *
bs`` positions: for each of the chunk's ``ppc`` table entries one DMA
copies that block's ``bs`` rows of every KV head (K and V) into a VMEM
buffer.  Only chunks below the length are read; a chunk's entries past
the slot's blocks point at the trash block or at blocks the slot holds
but has not written, and are masked.  Two buffers alternate: while one
chunk is attended the next is in flight, the next slot's first chunk
included, so the copies run ahead of the math across grid steps.

The math is :func:`~repro.kernels.paged_decode.ref.decode_attention`'s,
as an online softmax: scores ``q·k / sqrt(Dh)`` in float32 from the
cache dtype, positions at or past the length set to ``-1e30``, a running
max and sum per query head, ``p`` cast to the cache dtype for ``p·v``.

VMEM (bf16, Hkv=4, T=256, Dh=128): two K and two V buffers of
4×256×128×2 B = 256 KiB each, 1 MiB in all.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["paged_decode_pallas", "chunk_pages"]

NEG_INF = -1e30

# positions a chunk covers, at most: enough to keep the per-chunk work
# above the loop's fixed cost, few enough that the last chunk wastes little
_CHUNK_TOKENS = 256


def chunk_pages(block_size: int, table_width: int) -> int:
    """Table entries (blocks) read per chunk."""
    return max(1, min(table_width, _CHUNK_TOKENS // block_size))


def _kernel(
    tables_ref, lengths_ref, layer_ref,     # scalar prefetch (SMEM)
    q_ref, k_hbm, v_hbm,                    # inputs
    o_ref,                                  # output
    k_buf, v_buf, sems, parity_ref,         # scratch
    *, bs: int, ppc: int, width: int,
):
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    hkv, g, dh = q_ref.shape
    t = ppc * bs
    layer = layer_ref[0]

    def copies(slot, chunk, buf):
        out = []
        for j in range(ppc):
            entry = jnp.minimum(chunk * ppc + j, width - 1)
            rows = pl.ds(tables_ref[slot * width + entry] * bs, bs)
            dst = pl.ds(j * bs, bs)
            out.append(pltpu.make_async_copy(
                k_hbm.at[layer, :, rows, :], k_buf.at[buf, :, dst, :],
                sems.at[0, buf]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[layer, :, rows, :], v_buf.at[buf, :, dst, :],
                sems.at[1, buf]))
        return out

    def start(slot, chunk, buf):
        for c in copies(slot, chunk, buf):
            c.start()

    @pl.when(b == 0)
    def _first():
        parity_ref[0] = 0
        start(0, 0, 0)

    length = lengths_ref[b]
    n_chunks = (length + t - 1) // t
    first = parity_ref[0]
    root_dh = math.sqrt(dh)

    def body(i, carry):
        buf = (first + i) % 2

        @pl.when(i + 1 < n_chunks)
        def _next_chunk():
            start(b, i + 1, 1 - buf)

        @pl.when(jnp.logical_and(i + 1 == n_chunks, b + 1 < n_slots))
        def _next_slot():
            start(b + 1, 0, 1 - buf)

        for c in copies(b, i, buf):
            c.wait()
        pos = i * t + jax.lax.broadcasted_iota(jnp.int32, (g, t), 1)
        valid = pos < length
        out = []
        for h in range(hkv):
            m_prev, l_prev, acc = carry[h]
            k = k_buf[buf, h]                               # (T, Dh)
            v = v_buf[buf, h]
            s = jax.lax.dot_general(
                q_ref[h].astype(k.dtype), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) / root_dh                                     # (G, T)
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc = alpha * acc + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                               # (G, Dh)
            out.append((m_new, l_new, acc))
        return tuple(out)

    init = tuple(
        (jnp.full((g, 1), NEG_INF, jnp.float32), jnp.zeros((g, 1), jnp.float32),
         jnp.zeros((g, dh), jnp.float32))
        for _ in range(hkv)
    )
    res = jax.lax.fori_loop(0, n_chunks, body, init)
    for h in range(hkv):
        _, l_f, acc = res[h]
        o_ref[h] = acc / l_f
    parity_ref[0] = (first + n_chunks) % 2


def paged_decode_pallas(
    q: jax.Array,        # (B, H, Dh)
    k_pool: jax.Array,   # (L, Hkv, P, Dh)
    v_pool: jax.Array,
    tables: jax.Array,   # (B, M) int32
    lengths: jax.Array,  # (B,) int32, each >= 1
    layer,               # int32 scalar
    block_size: int,
    interpret: bool = False,
) -> jax.Array:
    """→ (B, H, Dh) float32.  Every length must be at least 1 (an idle
    slot attends position 0 of its trash block)."""
    from jax.experimental.pallas import tpu as pltpu

    b, h, dh = q.shape
    _, hkv, _, _ = k_pool.shape
    if h % hkv:
        raise ValueError(f"H={h} not a multiple of Hkv={hkv}")
    g = h // hkv
    width = tables.shape[1]
    ppc = chunk_pages(block_size, width)
    t = ppc * block_size
    qg = q.reshape(b, hkv, g, dh)
    block = pl.BlockSpec((None, hkv, g, dh), lambda i, *_: (i, 0, 0, 0))
    kernel = functools.partial(_kernel, bs=block_size, ppc=ppc, width=width)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                block,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((2, hkv, t, dh), k_pool.dtype),
                pltpu.VMEM((2, hkv, t, dh), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), jnp.float32),
        # the DMAs run ahead across grid steps: the steps must run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode",
    )(
        tables.reshape(-1).astype(jnp.int32),
        lengths.astype(jnp.int32),
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        qg, k_pool, v_pool,
    )
    return out.reshape(b, h, dh)
