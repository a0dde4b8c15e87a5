"""Similarity-search tests: fingerprint folding, Tanimoto backends
(oracle / blocked host / interpreted Pallas kernel) byte-parity, the
store's fingerprint sidecars, deterministic cross-shard tie-breaking,
and the service-level batched ``similar`` path (+ the asyncio fetch).
"""

import asyncio
import tempfile
from pathlib import Path

import numpy as np
import pytest

from repro.core import (
    ByteOffsetIndex,
    IndexStore,
    RecordStore,
    build_index,
    extract,
    intersect_host,
)
from repro.core.fingerprint import (
    DEFAULT_FP_BITS,
    _POP_LUT,
    fingerprint_batch,
    fold_fingerprint,
    popcount_u32,
    words_for,
)
from repro.core.sdfgen import CorpusSpec, db_id_list, generate_corpus
from repro.core.store import merge_similar_topk
from repro.kernels.tanimoto.ops import (
    tanimoto_topk,
    tanimoto_topk_host,
    tanimoto_topk_pallas,
)
from repro.kernels.tanimoto.ref import (
    PAD_INDEX,
    PAD_SCORE,
    tanimoto_topk_naive,
    tanimoto_topk_ref,
)
from repro.service import QueryService, ServiceConfig, ShardRouter


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

# repetitions of "ABC" share one trigram *set* {ABC, BCA, CAB}: distinct
# keys, byte-identical folded fingerprints — a seeded tie flood
TIE_KEYS = ["ABC" * r for r in range(2, 12)]


@pytest.fixture(scope="module")
def tie_store_dir():
    """Sharded store seeding equal-fingerprint keys across shards/files."""
    idx = ByteOffsetIndex(key_mode="full_id")
    for i, key in enumerate(TIE_KEYS):
        idx.add(key, f"f_{i % 4:02d}.sdf", 1000 + i * 64)
    for i in range(300):
        idx.add(f"FILLER/{i:05d}", f"f_{i % 4:02d}.sdf", 50_000 + i * 64)
    sdir = Path(tempfile.mkdtemp()) / "tie_store"
    idx.save_sharded(sdir, n_shards=8)
    return sdir, idx


@pytest.fixture(scope="module")
def corpus():
    spec = CorpusSpec(n_files=3, records_per_file=400, key_bits=16)
    root = Path(tempfile.mkdtemp()) / "corpus"
    generate_corpus(root, spec)
    return RecordStore(root), spec


@pytest.fixture(scope="module")
def corpus_store_dir(corpus):
    store, _ = corpus
    idx = build_index(store, key_mode="full_id")
    sdir = Path(tempfile.mkdtemp()) / "istore"
    idx.save_sharded(sdir, n_shards=8)
    return sdir, sorted(idx.entries.keys())


# ---------------------------------------------------------------------------
# fingerprint folding
# ---------------------------------------------------------------------------

def test_fold_deterministic_and_batch_consistent():
    texts = ["InChI=1S/C2H6O/c1-2-3/h3H,2H2,1H3", "xyz", "ab", ""]
    fps, counts = fingerprint_batch(texts)
    assert fps.shape == (4, words_for(DEFAULT_FP_BITS))
    for i, t in enumerate(texts):
        assert np.array_equal(fps[i], fold_fingerprint(t))
        assert counts[i] == popcount_u32(fps[i]).sum()
    again, _ = fingerprint_batch(texts)
    assert np.array_equal(fps, again)
    assert (counts[:2] > 0).all()


def test_equal_trigram_sets_collide():
    base = fold_fingerprint("ABCABC")
    for key in TIE_KEYS:
        assert np.array_equal(fold_fingerprint(key), base)
    assert not np.array_equal(fold_fingerprint("ABX"), base)


def test_words_for_validation():
    assert words_for(1024) == 32
    assert words_for(32) == 1
    for bad in (0, 16, 48, 96, -32):
        with pytest.raises(ValueError):
            words_for(bad)


def test_popcount_lut_matches_bitwise_count():
    rng = np.random.default_rng(3)
    a = rng.integers(0, 2**32, size=(37, 5), dtype=np.uint32)
    via_lut = _POP_LUT[np.ascontiguousarray(a).view(np.uint8)].reshape(
        *a.shape, 4
    ).sum(axis=-1, dtype=np.int32)
    assert np.array_equal(popcount_u32(a), via_lut)
    assert popcount_u32(np.uint32([0, 0xFFFFFFFF])).tolist() == [0, 32]


# ---------------------------------------------------------------------------
# backend byte-parity: oracle vs blocked host vs interpreted Pallas
# ---------------------------------------------------------------------------

def _rand_plane(rng, n, w):
    return rng.integers(0, 2**32, size=(n, w), dtype=np.uint32)


@pytest.mark.parametrize("qn,n,k", [(1, 1, 4), (7, 255, 8), (5, 3, 8)])
def test_host_backend_matches_oracle(qn, n, k):
    rng = np.random.default_rng(11)
    q, db = _rand_plane(rng, qn, 32), _rand_plane(rng, n, 32)
    if n >= 3:
        db[2] = db[0]  # duplicated rows: exact score ties
    ref = tanimoto_topk_ref(q, db, k)
    for kw in ({}, {"db_chunk": 100, "tile": 64}, {"tile": 7}):
        got = tanimoto_topk_host(q, db, k, **kw)
        assert np.array_equal(ref[0], got[0]) and np.array_equal(ref[1], got[1])


def test_host_backend_odd_width_and_empty():
    rng = np.random.default_rng(12)
    q, db = _rand_plane(rng, 3, 1), _rand_plane(rng, 40, 1)  # no uint64 view
    ref = tanimoto_topk_ref(q, db, 5)
    got = tanimoto_topk_host(q, db, 5)
    assert np.array_equal(ref[0], got[0]) and np.array_equal(ref[1], got[1])
    s, i = tanimoto_topk_host(np.zeros((2, 2), np.uint32),
                              np.zeros((0, 2), np.uint32), 3)
    assert (s == PAD_SCORE).all() and (i == PAD_INDEX).all()


def test_kernel_interpret_matches_oracle_with_ties():
    texts = ["ABCABC"] * 9 + [f"U{i:03d}" for i in range(30)]
    db, _ = fingerprint_batch(texts)
    q, _ = fingerprint_batch(["ABCABCABC", "U005"])
    ref = tanimoto_topk_ref(q, db, 6)
    kern = tanimoto_topk(q, db, 6, interpret=True)
    assert np.array_equal(ref[0], kern[0])
    assert np.array_equal(ref[1], kern[1])
    # the 9 identical rows tie at 1.0 and must surface lowest-row-first
    assert kern[1][0].tolist() == [0, 1, 2, 3, 4, 5]
    # k > n_db pads with the oracle sentinel
    s, i = tanimoto_topk(q[:1], db[:2], 5, interpret=True)
    assert (s[0, 2:] == PAD_SCORE).all() and (i[0, 2:] == PAD_INDEX).all()


@pytest.mark.parametrize("off", [-3, -1, 1, 3])
def test_kernel_round_quotient_corrects_an_inexact_divide(off):
    # the chip's divide is a few ulps off; the kernel's scores must still be
    # the IEEE quotient, so feed it estimates that are off on purpose
    import jax.numpy as jnp

    from repro.kernels.tanimoto.kernel import MAX_BITS, round_quotient

    rng = np.random.default_rng(15)
    u = np.concatenate([[1, 2, 3, 7, 1023, 4095, MAX_BITS],
                        rng.integers(1, MAX_BITS + 1, 4000)]).astype(np.int32)
    c = np.concatenate([u[:7], rng.integers(1, u[7:] + 1)]).astype(np.int32)
    c[:3] = 1
    want = c.astype(np.float32) / u.astype(np.float32)
    q0 = (want.view(np.int32) + off).view(np.float32)
    got = np.asarray(round_quotient(
        jnp.asarray(c, jnp.float32), jnp.asarray(u, jnp.float32), jnp.asarray(q0)
    ))
    assert np.array_equal(got.view(np.int32), want.view(np.int32))


def test_kernel_interpret_matches_oracle_past_one_heap_tile():
    # k above 128 spans two lanes tiles of the running top-k
    rng = np.random.default_rng(16)
    q, db = _rand_plane(rng, 3, 4), _rand_plane(rng, 300, 4)
    db[150] = db[7]  # a tie across the first tile's edge
    ref = tanimoto_topk_ref(q, db, 200)
    kern = tanimoto_topk(q, db, 200, interpret=True)
    assert np.array_equal(ref[0], kern[0]) and np.array_equal(ref[1], kern[1])


def test_store_refuses_fingerprints_the_kernel_cannot_score(tie_store_dir):
    from repro.core.fingerprint import MAX_FP_BITS

    sdir, idx = tie_store_dir
    with pytest.raises(ValueError, match="fingerprint_bits"):
        idx.save_sharded(sdir, n_shards=8, fingerprint_bits=2 * MAX_FP_BITS)
    assert IndexStore.open(sdir).fingerprint_bits == DEFAULT_FP_BITS


def test_kernel_refuses_fingerprints_wider_than_exact_scores_allow():
    from repro.kernels.tanimoto.kernel import MAX_BITS

    w = MAX_BITS // 32 + 1
    q = np.ones((1, w), np.uint32)
    with pytest.raises(ValueError, match="bits"):
        tanimoto_topk(q, q, 1, interpret=True)


def test_naive_loop_matches_batched():
    rng = np.random.default_rng(13)
    q, db = _rand_plane(rng, 6, 32), _rand_plane(rng, 90, 32)
    ref = tanimoto_topk_ref(q, db, 7)
    naive = tanimoto_topk_naive(q, db, 7)
    assert np.array_equal(ref[0], naive[0]) and np.array_equal(ref[1], naive[1])


def test_dispatcher_host_path_is_blocked_backend():
    rng = np.random.default_rng(14)
    q, db = _rand_plane(rng, 4, 32), _rand_plane(rng, 64, 32)
    auto = tanimoto_topk(q, db, 5, use_pallas=False)
    host = tanimoto_topk_host(q, db, 5)
    assert np.array_equal(auto[0], host[0]) and np.array_equal(auto[1], host[1])


# ---------------------------------------------------------------------------
# store sidecars + similar_batch
# ---------------------------------------------------------------------------

def test_fingerprint_sidecars_roundtrip_and_incremental(tie_store_dir):
    sdir, idx = tie_store_dir
    st = IndexStore.open(sdir)
    assert st.fingerprint_bits == DEFAULT_FP_BITS
    assert all((sdir / f"shard_{s:04d}.fps.npy").exists()
               for s in range(st.n_shards)
               if int(st.manifest["shards"][s]["count"]) > 0)
    # unchanged republish skips every shard (fingerprints are a pure
    # function of the keys the content hash already covers)
    assert idx.save_sharded(sdir, n_shards=8)["written"] == 0
    # a width change invalidates the plane and forces a rewrite
    summary = idx.save_sharded(sdir, n_shards=8, fingerprint_bits=512)
    assert summary["written"] > 0
    assert IndexStore.open(sdir).fingerprint_bits == 512
    # disabling the plane cleans the sidecars up and similarity errors
    idx.save_sharded(sdir, n_shards=8, fingerprint_bits=None)
    st = IndexStore.open(sdir)
    assert st.fingerprint_bits is None
    assert not list(sdir.glob("*.fps.npy"))
    with pytest.raises(ValueError, match="no fingerprint plane"):
        st.similar_batch(np.zeros((1, 32), np.uint32), 4)
    # exact-key lookup is untouched by the plane's absence
    assert st.lookup_batch(TIE_KEYS[:3])[2].all()
    idx.save_sharded(sdir, n_shards=8)  # restore for later tests


def test_store_similar_matches_bruteforce_oracle(corpus_store_dir):
    sdir, keys = corpus_store_dir
    st = IndexStore.open(sdir)
    q, _ = fingerprint_batch(keys[::150][:8])
    scores, fids, offs = st.similar_batch(q, 5, probe="host")
    # brute force: score the whole corpus per shard, merge on the
    # two-level contract (score desc, file_id asc, offset asc)
    parts = []
    for s in range(st.n_shards):
        if int(st.manifest["shards"][s]["count"]) == 0:
            continue
        parts.append(st.similar_shard(s, q, 5, probe="host"))
    want = merge_similar_topk(parts, 5)
    assert np.array_equal(scores, want[0])
    assert np.array_equal(fids, want[1])
    assert np.array_equal(offs, want[2])
    # every query is a corpus key: rank-0 must be its own location, 1.0
    assert (scores[:, 0] == np.float32(1.0)).all()
    locs = st.locate_batch(keys[::150][:8])
    for i, loc in enumerate(locs):
        assert loc == (st.file_names[fids[i, 0]], int(offs[i, 0]))


def test_cross_shard_ties_break_by_file_then_offset(tie_store_dir):
    sdir, idx = tie_store_dir
    st = IndexStore.open(sdir)
    # the tie keys land on multiple shards (that's the point of the test)
    q = fold_fingerprint("ABCABC")[None, :]
    k = 4
    scores, fids, offs = st.similar_batch(q, k, probe="host")
    assert (scores[0] == np.float32(1.0)).all()
    # expected: all equal-score candidates ordered (file_id, offset)
    fmap = {name: i for i, name in enumerate(st.file_names)}
    cands = sorted(
        (fmap[f], o) for f, o in (idx.lookup(key) for key in TIE_KEYS)
    )
    assert [(int(f), int(o)) for f, o in zip(fids[0], offs[0])] == cands[:k]
    # shards were actually spanned, not one lucky bucket
    shard_span = {
        s for s in range(st.n_shards)
        for key in TIE_KEYS
        if st.lookup_batch([key])[2][0]
    }
    from repro.core.store import digest_u64, shard_of
    sids = shard_of(digest_u64(TIE_KEYS), st.n_shards, st.digest_bits)
    assert len(set(sids.tolist())) > 1


def test_merge_similar_topk_pads_and_ties():
    a = (
        np.array([[1.0, 0.5, 0.5]], np.float32),
        np.array([[2, 0, 3]], np.int32),
        np.array([[10, 99, 4]], np.int64),
    )
    b = (
        np.array([[1.0, -1.0, -1.0]], np.float32),
        np.array([[1, -1, -1]], np.int32),
        np.array([[7, -1, -1]], np.int64),
    )
    s, f, o = merge_similar_topk([a, b], 3)
    assert s[0].tolist() == [1.0, 1.0, 0.5]
    assert f[0].tolist() == [1, 2, 0]      # equal scores: file_id asc
    assert o[0].tolist() == [7, 10, 99]
    s, f, o = merge_similar_topk([b], 3)   # pads sort last, stay -1
    assert f[0].tolist() == [1, -1, -1] and s[0, 1] == PAD_SCORE


# ---------------------------------------------------------------------------
# router + service
# ---------------------------------------------------------------------------

def test_router_scatter_matches_inline(corpus_store_dir):
    sdir, keys = corpus_store_dir
    q, _ = fingerprint_batch(keys[::97][:6])
    with ShardRouter(sdir, replicas=2, probe="host") as rt:
        scattered = rt.similar_batch(q, 4)
        assert rt.stats.similar_scattered == 1
    with ShardRouter(sdir, replicas=1, probe="host") as rt:
        inline = rt.similar_batch(q, 4)
        assert rt.stats.similar_inline == 1
    for got, want in zip(scattered, inline):
        assert np.array_equal(got, want)


def test_service_similar_coalesces_and_slices(corpus, corpus_store_dir):
    store, _ = corpus
    sdir, keys = corpus_store_dir
    q, _ = fingerprint_batch(keys[::50][:8])
    with QueryService(store, sdir, ServiceConfig(replicas=2)) as svc:
        import threading
        outs = {}
        def client(i):
            outs[i] = svc.similar(q[i : i + 2], 3)
        ths = [threading.Thread(target=client, args=(i,)) for i in range(6)]
        for t in ths: t.start()
        for t in ths: t.join()
        st = IndexStore.open(sdir)
        for i, (s, f, o, deg) in outs.items():
            assert not deg.any()
            ws, wf, wo = st.similar_batch(q[i : i + 2], 3, probe="host")
            assert np.array_equal(s, ws) and np.array_equal(f, wf)
            assert np.array_equal(o, wo)
        sim = svc.stats()["similarity"]
        assert sim["scheduler"]["requests"] == 6
        # a 1-D query row is accepted; k above the probe width bypasses
        # the batcher but returns the same contract
        s1, f1, o1, _ = svc.similar(q[0], 2)
        assert s1.shape == (1, 2)
        big = svc.similar(q[:2], svc.config.similar_top_k + 8)
        assert big[0].shape == (2, svc.config.similar_top_k + 8)
        with pytest.raises(ValueError):
            svc.similar(q[:1], 0)


def test_service_similar_async_event_loop(corpus, corpus_store_dir):
    store, _ = corpus
    sdir, keys = corpus_store_dir
    q, _ = fingerprint_batch(keys[:4])
    with QueryService(store, sdir, ServiceConfig(replicas=1)) as svc:
        async def go():
            futs = [svc.similar_async(q[i : i + 1], 3) for i in range(4)]
            return [await asyncio.wrap_future(f) for f in futs]
        outs = asyncio.run(go())
        st = IndexStore.open(sdir)
        for i, (s, f, o, _) in enumerate(outs):
            ws, wf, wo = st.similar_batch(q[i : i + 1], 3, probe="host")
            assert np.array_equal(s, ws) and np.array_equal(f, wf)
            assert np.array_equal(o, wo)


def test_fetch_aio_matches_fetch(corpus):
    """satellite: the asyncio fetch path is byte-identical to fetch()."""
    store, spec = corpus
    targets = intersect_host(
        db_id_list(spec, "chembl", extra_outside=10),
        db_id_list(spec, "emolecules", extra_outside=10),
    ).ids
    idx = build_index(store, key_mode="hashed_key", key_bits=16)
    sdir = Path(tempfile.mkdtemp()) / "istore_aio"
    idx.save_sharded(sdir, n_shards=8)
    serial = extract(store, idx, targets, key_bits=16, workers=0)
    with QueryService(store, sdir, ServiceConfig(replicas=2)) as svc:
        sync = svc.fetch(targets, key_bits=16)

        async def go():
            return await svc.fetch_aio(targets, key_bits=16)

        aio = asyncio.run(go())
    for res in (sync, aio):
        assert list(res.records.items()) == list(serial.records.items())
        assert res.missing == serial.missing
        assert res.mismatches == serial.mismatches
