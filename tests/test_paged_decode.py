"""Paged decode attention: the Pallas kernel (interpret mode) against its
gather-then-attend reference.

Each case puts slots' blocks at scattered pool rows of two layers and
asks the kernel for one of them: ragged lengths; lengths of 1, one block
and the whole table; idle slots on the trash block; GQA groups of 8 over
4 KV heads and over 1 (a tensor-parallel shard); head widths of 128 and
32; bfloat16 and float32 pools.  The kernel must also leave alone every
block past a slot's last chunk: those hold NaN here, and the answer must
stay finite and equal to the reference's on a clean pool.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.paged_decode.kernel import chunk_pages, paged_decode_pallas
from repro.kernels.paged_decode.ops import paged_decode, tiles_fit
from repro.kernels.paged_decode.ref import paged_decode_ref

BS = 16

# traced once per shape: both layers of a case share the program
kernel = jax.jit(functools.partial(paged_decode_pallas, block_size=BS,
                                   interpret=True))
reference = jax.jit(functools.partial(paged_decode_ref, block_size=BS))


def _case(rng, hkv, g, dh, lengths, width, dtype, layers=2):
    b = len(lengths)
    n_blocks = b * width + 1
    shape = (layers, hkv, n_blocks * BS, dh)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    q = rng.standard_normal((b, hkv * g, dh)).astype(np.float32)
    # every slot's blocks scattered over the pool, block 0 kept for trash
    tables = rng.permutation(np.arange(1, n_blocks))[: b * width]
    tables = tables.reshape(b, width).astype(np.int32)
    return q, k, v, tables, np.asarray(lengths, np.int32)


def _check(q, k, v, tables, lengths, dtype, layer, tol):
    cast = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    args = (cast(q), cast(k), cast(v), jnp.asarray(tables), jnp.asarray(lengths),
            jnp.int32(layer))
    got = kernel(*args)
    want = reference(*args)
    assert got.shape == q.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol, rtol=tol)


CASES = {
    # name: (hkv, g, dh, lengths, width, dtype)
    "ragged": (4, 8, 128, [37, 200, 5, 129, 256, 91], 16, jnp.bfloat16),
    "edges": (4, 8, 128, [1, BS, 16 * BS, BS + 1, 2 * BS], 16, jnp.bfloat16),
    "one_kv_head": (1, 8, 128, [300, 17, 1, 64], 32, jnp.bfloat16),
    "narrow_head": (2, 4, 32, [3, 48, 100, 128], 8, jnp.float32),
    "f32_wide": (4, 8, 128, [70, 1, 513, 256], 48, jnp.float32),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_paged_decode_matches_reference(name):
    hkv, g, dh, lengths, width, dtype = CASES[name]
    rng = np.random.default_rng(sorted(CASES).index(name))
    q, k, v, tables, lengths = _case(rng, hkv, g, dh, lengths, width, dtype)
    # bfloat16: the kernel casts unnormalised probabilities for p·v where
    # the reference casts normalised ones, one bfloat16 rounding apart
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for layer in (0, 1):
        _check(q, k, v, tables, lengths, dtype, layer, tol)


def test_idle_slots_read_the_trash_block():
    rng = np.random.default_rng(7)
    q, k, v, tables, _ = _case(rng, 4, 8, 128, [1] * 4, 8, jnp.bfloat16)
    tables[1:] = 0                      # idle slots: every entry on trash
    lengths = np.asarray([40, 1, 1, 1], np.int32)
    _check(q, k, v, tables, lengths, jnp.bfloat16, 1, 2e-2)


def test_blocks_past_the_last_chunk_are_never_read():
    rng = np.random.default_rng(3)
    width = 40
    lengths = [1, 300, 17, 600]
    q, k, v, tables, lengths = _case(rng, 4, 8, 128, lengths, width, jnp.float32)
    t = chunk_pages(BS, width) * BS
    k_bad, v_bad = k.copy(), v.copy()
    for row, n in zip(tables, lengths):
        live = -(-n // t) * t // BS     # table entries of the chunks read
        for blk in row[live:]:
            k_bad[:, :, blk * BS:(blk + 1) * BS] = np.nan
            v_bad[:, :, blk * BS:(blk + 1) * BS] = np.nan
    got = kernel(jnp.asarray(q), jnp.asarray(k_bad), jnp.asarray(v_bad),
                 jnp.asarray(tables), jnp.asarray(lengths), jnp.int32(1))
    want = reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                     jnp.asarray(tables), jnp.asarray(lengths), jnp.int32(1))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_entry_point_takes_the_reference_off_tpu_and_for_unfit_tiles():
    assert tiles_fit(128, 16, jnp.bfloat16) and tiles_fit(256, 8, jnp.float32)
    assert not tiles_fit(64, 16, jnp.bfloat16)      # part of a lane row
    assert not tiles_fit(128, 8, jnp.bfloat16)      # half a packed tile
    rng = np.random.default_rng(1)
    q, k, v, tables, lengths = _case(rng, 2, 2, 32, [5, 20], 4, jnp.float32)
    args = tuple(map(jnp.asarray, (q, k, v, tables, lengths))) + (jnp.int32(0),)
    hlo = jax.jit(lambda *a: paged_decode(*a, block_size=BS)).lower(*args).as_text()
    assert "tpu_custom_call" not in hlo
    np.testing.assert_array_equal(
        np.asarray(paged_decode(*args, block_size=BS)),
        np.asarray(paged_decode_ref(*args, BS)))
