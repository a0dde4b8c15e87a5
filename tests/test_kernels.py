"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle.

Every kernel sweeps shapes/dtypes and asserts allclose (bit-exact for the
integer kernels) against its ref.py oracle, plus hypothesis property tests
on the kernels' semantic invariants.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.kernels.hash_mix.kernel import hash_mix_pallas
from repro.kernels.hash_mix.ref import hash_mix_ref
from repro.kernels.sorted_probe.ops import sorted_probe_pallas
from repro.kernels.sorted_probe.ref import sorted_probe_ref, sort_pairs
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import (
    flash_attention_chunked,
    flash_attention_ref,
)
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.kernels.ssd_scan.ref import ssd_scan_ref


# ---------------------------------------------------------------------------
# hash_mix
# ---------------------------------------------------------------------------

HASH_SHAPES = [(1, 8), (37, 16), (256, 8), (1000, 24), (4096, 64), (513, 8), (8, 40)]


@pytest.mark.parametrize("n,w", HASH_SHAPES)
def test_hash_mix_matches_ref(n, w):
    rng = np.random.default_rng(n * 1000 + w)
    x = jnp.asarray(rng.integers(0, 2**32, size=(n, w), dtype=np.uint32))
    ref = hash_mix_ref(x)
    pal = hash_mix_pallas(x, interpret=True)
    assert ref.shape == (n, 4) and ref.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))


@pytest.mark.parametrize("block_rows", [8, 64, 1024, 2048, 4096])
def test_hash_mix_block_size_invariance(block_rows):
    # 9000 ids span several (8, 128)-tile grid steps at every block size
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.integers(0, 2**32, size=(9000, 16), dtype=np.uint32))
    ref = hash_mix_ref(x)
    pal = hash_mix_pallas(x, block_rows=block_rows, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))


def test_hash_mix_seed_changes_digest():
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.integers(0, 2**32, size=(64, 8), dtype=np.uint32))
    a = np.asarray(hash_mix_ref(x, seed=0))
    b = np.asarray(hash_mix_ref(x, seed=1))
    assert not np.array_equal(a, b)


def test_hash_mix_avalanche():
    """Single input-bit flip flips ~half the output bits."""
    rng = np.random.default_rng(9)
    x = rng.integers(0, 2**32, size=(2000, 16), dtype=np.uint32)
    y = x.copy()
    y[:, 5] ^= 1 << 17
    hx = np.asarray(hash_mix_ref(jnp.asarray(x))).view(np.uint8)
    hy = np.asarray(hash_mix_ref(jnp.asarray(y))).view(np.uint8)
    rate = np.unpackbits(hx ^ hy, axis=1).mean()
    assert 0.47 < rate < 0.53


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 200),
    w=st.sampled_from([8, 16, 24]),
    seed=st.integers(0, 2**31 - 1),
)
def test_hash_mix_property_kernel_eq_ref(n, w, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, 2**32, size=(n, w), dtype=np.uint32))
    np.testing.assert_array_equal(
        np.asarray(hash_mix_ref(x)),
        np.asarray(hash_mix_pallas(x, block_rows=64, interpret=True)),
    )


def test_hash_mix_row_locality():
    """Digest of a row is independent of its neighbours (padding safety)."""
    rng = np.random.default_rng(10)
    x = rng.integers(0, 2**32, size=(50, 8), dtype=np.uint32)
    full = np.asarray(hash_mix_ref(jnp.asarray(x)))
    one = np.asarray(hash_mix_ref(jnp.asarray(x[20:21])))
    np.testing.assert_array_equal(full[20:21], one)


# ---------------------------------------------------------------------------
# sorted_probe
# ---------------------------------------------------------------------------

def _mk_table_queries(rng, m, q, hit_frac=0.5):
    t = rng.integers(0, 2**32, size=(m, 2), dtype=np.uint32)
    t = np.unique(
        t.view([("hi", np.uint32), ("lo", np.uint32)])
    ).view(np.uint32).reshape(-1, 2)
    nhit = int(q * hit_frac)
    qs = np.vstack(
        [
            t[rng.integers(0, len(t), nhit)],
            rng.integers(0, 2**32, size=(q - nhit, 2), dtype=np.uint32),
        ]
    )
    rng.shuffle(qs)
    return jnp.asarray(qs), jnp.asarray(t)


def _numpy_truth(qs, t):
    tn, qn = np.asarray(t), np.asarray(qs)
    tv = tn[:, 0].astype(np.uint64) << np.uint64(32) | tn[:, 1].astype(np.uint64)
    qv = qn[:, 0].astype(np.uint64) << np.uint64(32) | qn[:, 1].astype(np.uint64)
    pos = np.searchsorted(tv, qv, side="left")
    found = (pos < len(tv)) & (tv[np.minimum(pos, len(tv) - 1)] == qv)
    return found, pos.astype(np.int32)


PROBE_CASES = [
    (100, 50, 512, None),
    (5000, 1000, 512, None),
    (10000, 4096, 2048, None),
    (300, 7, 128, None),
    (65536, 4096, 2048, None),
    (2000, 1024, 2048, 8),     # forces overflow fallback
    (2000, 512, 256, 16),
]


@pytest.mark.parametrize("m,q,bt,qmax", PROBE_CASES)
def test_sorted_probe_matches_numpy(m, q, bt, qmax):
    rng = np.random.default_rng(m + q)
    qs, t = _mk_table_queries(rng, m, q)
    found_np, pos_np = _numpy_truth(qs, t)
    f_ref, p_ref = sorted_probe_ref(qs, t)
    np.testing.assert_array_equal(np.asarray(f_ref), found_np)
    np.testing.assert_array_equal(np.asarray(p_ref), pos_np)
    f_pal, p_pal = sorted_probe_pallas(qs, t, table_block=bt, qmax=qmax, interpret=True)
    np.testing.assert_array_equal(np.asarray(f_pal), found_np)
    np.testing.assert_array_equal(np.asarray(p_pal), pos_np)


def test_sorted_probe_all_hits_and_all_misses():
    rng = np.random.default_rng(11)
    qs, t = _mk_table_queries(rng, 4096, 512, hit_frac=1.0)
    f, _ = sorted_probe_pallas(qs, t, table_block=512, interpret=True)
    assert bool(jnp.all(f))
    qs2 = jnp.asarray(np.asarray(qs) ^ np.uint32(0x80000001))  # near-certain misses
    f2, _ = sorted_probe_ref(qs2, t)
    f2_np, _ = _numpy_truth(qs2, t)
    np.testing.assert_array_equal(np.asarray(f2), f2_np)


@settings(max_examples=25, deadline=None)
@given(
    m=st.integers(2, 400),
    q=st.integers(1, 200),
    seed=st.integers(0, 2**31 - 1),
)
def test_sorted_probe_property(m, q, seed):
    rng = np.random.default_rng(seed)
    qs, t = _mk_table_queries(rng, m, q, hit_frac=0.7)
    found_np, pos_np = _numpy_truth(qs, t)
    f, p = sorted_probe_pallas(qs, t, table_block=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(f), found_np)
    np.testing.assert_array_equal(np.asarray(p), pos_np)


def test_sort_pairs_is_lexicographic():
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.integers(0, 2**32, size=(500, 2), dtype=np.uint32))
    s, order = sort_pairs(x)
    sn = np.asarray(s)
    v = sn[:, 0].astype(np.uint64) << np.uint64(32) | sn[:, 1].astype(np.uint64)
    assert np.all(v[1:] >= v[:-1])
    # permutation property
    assert sorted(np.asarray(order).tolist()) == list(range(500))


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

FA_CASES = [
    # (B, Hq, Hkv, Sq, Skv, D, causal, window)
    (1, 2, 2, 256, 256, 64, True, None),
    (2, 4, 2, 256, 256, 64, True, None),
    (1, 2, 1, 128, 384, 32, True, None),
    (1, 2, 2, 256, 256, 64, True, 128),
    (1, 4, 4, 256, 256, 128, False, None),
    (1, 8, 1, 128, 128, 64, True, None),   # MQA
]


@pytest.mark.parametrize("b,hq,hkv,sq,skv,d,causal,window", FA_CASES)
def test_flash_attention_matches_ref_f32(b, hq, hkv, sq, skv, d, causal, window):
    rng = np.random.default_rng(b * 100 + hq)
    q = jnp.asarray(rng.standard_normal((b, hq, sq, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, hkv, skv, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, hkv, skv, d)).astype(np.float32))
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    pal = flash_attention_pallas(
        q, k, v, causal=causal, window=window,
        block_q=128, block_k=128, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_dtypes(dtype):
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), dtype=dtype)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), dtype=dtype)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), dtype=dtype)
    ref = flash_attention_ref(q, k, v)
    pal = flash_attention_pallas(q, k, v, block_q=128, block_k=128, interpret=True)
    assert pal.dtype == dtype
    atol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(ref, dtype=np.float32), np.asarray(pal, dtype=np.float32), atol=atol
    )


def test_flash_attention_block_size_invariance():
    rng = np.random.default_rng(14)
    q = jnp.asarray(rng.standard_normal((1, 2, 512, 64)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, 2, 512, 64)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((1, 2, 512, 64)).astype(np.float32))
    a = flash_attention_pallas(q, k, v, block_q=128, block_k=256, interpret=True)
    b = flash_attention_pallas(q, k, v, block_q=256, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


def test_flash_attention_causality_property():
    """Perturbing future keys must not change past outputs."""
    rng = np.random.default_rng(15)
    q = jnp.asarray(rng.standard_normal((1, 2, 256, 32)).astype(np.float32))
    k = np.asarray(rng.standard_normal((1, 2, 256, 32)).astype(np.float32))
    v = np.asarray(rng.standard_normal((1, 2, 256, 32)).astype(np.float32))
    out1 = flash_attention_pallas(
        q, jnp.asarray(k), jnp.asarray(v), block_q=128, block_k=128, interpret=True
    )
    k2, v2 = k.copy(), v.copy()
    k2[:, :, 200:], v2[:, :, 200:] = 99.0, -99.0
    out2 = flash_attention_pallas(
        q, jnp.asarray(k2), jnp.asarray(v2), block_q=128, block_k=128, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out1)[:, :, :200], np.asarray(out2)[:, :, :200], atol=1e-6
    )


@pytest.mark.parametrize("sq,skv", [(1040, 1040), (40, 1040), (200, 200)])
def test_flash_attention_any_length_matches_ref(sq, skv):
    """Lengths that are not block multiples pad and mask, not raise."""
    rng = np.random.default_rng(sq + skv)
    q = jnp.asarray(rng.standard_normal((1, 4, sq, 32)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, 2, skv, 32)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((1, 2, skv, 32)).astype(np.float32))
    ref = flash_attention_ref(q, k, v)
    pal = flash_attention_pallas(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_suffix_rows_bit_identical_to_full():
    """A suffix query block reproduces the full prefill's rows exactly
    (the prefix-cache parity promise), whatever the query blocking."""
    rng = np.random.default_rng(18)
    skv, suf = 1040, 48
    q = jnp.asarray(rng.standard_normal((1, 4, skv, 32)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, 2, skv, 32)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((1, 2, skv, 32)).astype(np.float32))
    full = flash_attention_pallas(q, k, v, interpret=True)
    tail = flash_attention_pallas(q[:, :, -suf:], k, v, interpret=True)
    np.testing.assert_array_equal(np.asarray(full)[:, :, -suf:],
                                  np.asarray(tail))


@pytest.mark.parametrize("b,hq,hkv,sq,skv,d,causal,window,chunk", [
    (1, 2, 2, 256, 256, 64, True, None, 128),
    (2, 4, 2, 256, 256, 64, True, None, 96),    # chunk not dividing skv
    (1, 2, 1, 128, 384, 32, True, None, 128),   # Sq < Skv
    (1, 2, 2, 256, 256, 64, True, 128, 64),     # sliding window
    (1, 4, 4, 192, 192, 48, False, None, 128),
])
def test_flash_attention_chunked_matches_ref(b, hq, hkv, sq, skv, d,
                                             causal, window, chunk):
    """The XLA online-softmax path vs the oracle."""
    rng = np.random.default_rng(b * 31 + sq)
    q = jnp.asarray(rng.standard_normal((b, hq, sq, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, hkv, skv, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, hkv, skv, d)).astype(np.float32))
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    chk = flash_attention_chunked(
        q, k, v, causal=causal, window=window, chunk=chunk
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(chk),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("b,hq,hkv,sq,skv,d,causal,window", FA_CASES + [
    (1, 4, 2, 64, 1040, 32, True, None),        # Skv past one 1024 chunk
])
def test_flash_attention_entry_xla_path_matches_ref(b, hq, hkv, sq, skv, d,
                                                    causal, window):
    """The entry point off the Pallas path (every non-TPU platform) vs the
    oracle, at its default chunk."""
    rng = np.random.default_rng(b * 7 + skv)
    q = jnp.asarray(rng.standard_normal((b, hq, sq, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, hkv, skv, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, hkv, skv, d)).astype(np.float32))
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          use_pallas=False)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               atol=3e-5, rtol=3e-5)


@settings(max_examples=15, deadline=None)
@given(
    sq=st.sampled_from([64, 128]),
    skv=st.sampled_from([128, 192]),
    chunk=st.sampled_from([32, 64, 96]),
    seed=st.integers(0, 2**31 - 1),
)
def test_flash_attention_chunked_property(sq, skv, chunk, seed):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((1, 2, sq, 32)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, 2, skv, 32)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((1, 2, skv, 32)).astype(np.float32))
    ref = flash_attention_ref(q, k, v)
    chk = flash_attention_chunked(q, k, v, chunk=chunk)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(chk),
                               atol=3e-5, rtol=3e-5)


def test_flash_attention_window_equals_full_when_window_ge_seq():
    rng = np.random.default_rng(16)
    q = jnp.asarray(rng.standard_normal((1, 2, 256, 32)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 32)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 32)).astype(np.float32))
    full = flash_attention_pallas(q, k, v, block_q=128, block_k=128, interpret=True)
    win = flash_attention_pallas(
        q, k, v, window=256, block_q=128, block_k=128, interpret=True
    )
    np.testing.assert_allclose(np.asarray(full), np.asarray(win), atol=1e-6)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

SSD_CASES = [(2, 4, 8, 16), (6, 16, 64, 128), (1, 1, 4, 4), (3, 32, 16, 32)]


@pytest.mark.parametrize("bh,c,p,n", SSD_CASES)
def test_ssd_scan_matches_ref(bh, c, p, n):
    rng = np.random.default_rng(bh * 10 + c)
    states = jnp.asarray(rng.standard_normal((bh, c, p, n)).astype(np.float32))
    decay = jnp.asarray(rng.uniform(0.2, 0.99, (bh, c)).astype(np.float32))
    ref = ssd_scan_ref(states, decay)
    pal = ssd_scan_pallas(states, decay, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal), atol=1e-6)


def test_ssd_scan_prefix_semantics():
    """prefix[0] == 0 and prefix[c+1] == decay[c]*prefix[c] + states[c]."""
    rng = np.random.default_rng(17)
    states = jnp.asarray(rng.standard_normal((2, 5, 4, 4)).astype(np.float32))
    decay = jnp.asarray(rng.uniform(0.5, 0.9, (2, 5)).astype(np.float32))
    pre = np.asarray(ssd_scan_pallas(states, decay, interpret=True))
    s, d = np.asarray(states), np.asarray(decay)
    np.testing.assert_allclose(pre[:, 0], 0.0)
    for c in range(4):
        np.testing.assert_allclose(
            pre[:, c + 1],
            d[:, c][:, None, None] * pre[:, c] + s[:, c],
            atol=1e-6,
        )


@settings(max_examples=20, deadline=None)
@given(
    bh=st.integers(1, 4),
    c=st.integers(1, 12),
    p=st.sampled_from([4, 8]),
    n=st.sampled_from([4, 16]),
    seed=st.integers(0, 2**31 - 1),
)
def test_ssd_scan_property(bh, c, p, n, seed):
    rng = np.random.default_rng(seed)
    states = jnp.asarray(rng.standard_normal((bh, c, p, n)).astype(np.float32))
    decay = jnp.asarray(rng.uniform(0.0, 1.0, (bh, c)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(ssd_scan_ref(states, decay)),
        np.asarray(ssd_scan_pallas(states, decay, interpret=True)),
        atol=1e-6,
    )
