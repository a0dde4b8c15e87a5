"""Sharded mmap-backed IndexStore + Bloom prefilter tests.

Covers the query-service layer's contract: shard-boundary routing, the
digest-collision verify path (narrow-digest seeding), mmap reopen after
``save_sharded``, Bloom false-positive handling, incremental re-publish,
device-probe parity, and ``lookup_batch`` parity with per-key
``ByteOffsetIndex.lookup`` — including a ≥100k-key corpus with seeded
digest collisions.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    BloomFilter,
    ByteOffsetIndex,
    IndexStore,
    RecordStore,
    build_index,
    candidate_runs,
    digest_u64,
    extract,
    intersect_host,
    intersect_sorted,
    save_sharded,
    shard_of,
)
from repro.core.sdfgen import CorpusSpec, db_id_list, generate_corpus


def synth_index(n: int, n_files: int = 7) -> ByteOffsetIndex:
    idx = ByteOffsetIndex(key_mode="full_id")
    for i in range(n):
        idx.add(f"InChI=1S/synthetic/{i}", f"f_{i % n_files:02d}.sdf", i * 100)
    return idx


# ---------------------------------------------------------------------------
# Bloom filter
# ---------------------------------------------------------------------------

def test_bloom_no_false_negatives_and_bounded_fpr():
    rng = np.random.default_rng(0)
    present = rng.integers(0, 2**63, size=4096, dtype=np.uint64)
    absent = rng.integers(0, 2**63, size=4096, dtype=np.uint64)
    absent = np.setdiff1d(absent, present)
    bf = BloomFilter.build(present, bits_per_key=12)
    assert bf.contains(present).all()  # never a false negative
    fpr = bf.contains(absent).mean()
    # 12 bits/key ≈ 0.5% theoretical; allow generous slack
    assert fpr < 0.05, fpr
    assert bf.expected_fpp(len(present)) < 0.02


def test_bloom_empty_and_tiny():
    bf = BloomFilter.build(np.array([], dtype=np.uint64))
    assert bf.contains(np.array([1, 2, 3], dtype=np.uint64)).sum() == 0
    one = np.array([42], dtype=np.uint64)
    bf = BloomFilter.build(one)
    assert bf.contains(one).all()


# ---------------------------------------------------------------------------
# save_sharded / IndexStore round trip
# ---------------------------------------------------------------------------

def test_save_sharded_reopen_parity_and_mmap(tmp_path):
    idx = synth_index(3000)
    summary = idx.save_sharded(tmp_path / "store", n_shards=8)
    assert summary == {
        "written": 8, "skipped": 0, "n_entries": 3000,
        "path": str(tmp_path / "store"),
    }
    qs = IndexStore.open(tmp_path / "store")
    assert len(qs) == 3000 and qs.key_mode == "full_id"

    keys = [f"InChI=1S/synthetic/{i}" for i in range(0, 3000, 11)]
    misses = [f"InChI=1S/absent/{i}" for i in range(40)]
    fid, off, hit = qs.lookup_batch(keys + misses)
    assert hit[: len(keys)].all() and not hit[len(keys):].any()
    assert (fid[len(keys):] == -1).all() and (off[len(keys):] == -1).all()
    for k, loc in zip(keys + misses, qs.locate_batch(keys + misses)):
        assert loc == idx.lookup(k)
    # columns of a touched shard are memory-mapped, not copied
    touched = next(iter(qs.stats.shards_touched))
    assert isinstance(qs._shard(touched).digests, np.memmap)
    # single-key compatibility surface
    assert qs.lookup(keys[0]) == idx.lookup(keys[0])
    assert keys[0] in qs and misses[0] not in qs


def test_shards_load_lazily(tmp_path):
    idx = synth_index(2000)
    idx.save_sharded(tmp_path / "s", n_shards=16)
    qs = IndexStore.open(tmp_path / "s")
    assert qs.shards_loaded == 0  # open() touches only the manifest
    # find a key and query it: exactly one shard may fault in
    key = "InChI=1S/synthetic/123"
    assert qs.lookup(key) == idx.lookup(key)
    assert qs.shards_loaded == 1
    d = digest_u64([key], bits=qs.digest_bits)
    assert set(qs.stats.shards_touched) == {
        int(shard_of(d, qs.n_shards, qs.digest_bits)[0])
    }
    # a bloom-rejected miss loads no further shard columns
    before = qs.shards_loaded
    rejected = None
    for i in range(200):
        probe = f"InChI=1S/absent/{i}"
        r0 = qs.stats.bloom_rejects
        qs.lookup(probe)
        if qs.stats.bloom_rejects > r0:
            rejected = probe
            break
    assert rejected is not None
    assert qs.shards_loaded == before


def test_shard_boundary_keys(tmp_path):
    """Keys whose digests sit at the edges of a shard's range route and
    resolve correctly (an off-by-one in `shard_of` or the per-shard search
    would lose exactly these)."""
    digest_bits, n_shards = 12, 4
    span = np.uint64(1 << (digest_bits - 2))  # digest range per shard
    idx = ByteOffsetIndex(key_mode="full_id")
    # hunt keys landing on the first/last digest value of a shard range
    cand = [f"InChI=1S/boundary/{i}" for i in range(20_000)]
    d = digest_u64(cand, bits=digest_bits)
    rem = d % span
    picks = np.nonzero((rem == 0) | (rem == span - np.uint64(1)))[0][:6]
    assert len(picks) == 6, "boundary-key hunt came up short"
    boundary_keys = [cand[int(i)] for i in picks]
    for i in picks:
        idx.add(cand[int(i)], "b.sdf", int(i))
    for j in range(500):  # filler spread across shards
        idx.add(f"InChI=1S/fill/{j}", "f.sdf", j)
    idx.save_sharded(tmp_path / "s", n_shards=n_shards, digest_bits=digest_bits)
    qs = IndexStore.open(tmp_path / "s")
    assert qs.locate_batch(boundary_keys) == [idx.lookup(k) for k in boundary_keys]


def test_digest_collision_verify_path(tmp_path):
    """At 8 effective digest bits nearly every digest collides; the
    equal-run scan + full-key verify must still resolve every key to ITS
    location and reject absent keys that alias a present digest."""
    idx = synth_index(600)
    idx.save_sharded(tmp_path / "s", n_shards=4, digest_bits=8)
    qs = IndexStore.open(tmp_path / "s")
    keys = [f"InChI=1S/synthetic/{i}" for i in range(600)]
    assert qs.locate_batch(keys) == [idx.lookup(k) for k in keys]
    assert qs.stats.verify_collisions > 0  # the run scan actually ran
    # absent keys: with 256 digest values every miss aliases some present
    # digest — verification must turn them all into clean misses
    absent = [f"InChI=1S/absent/{i}" for i in range(200)]
    _, _, hit = qs.lookup_batch(absent)
    assert not hit.any()


def test_bloom_false_positive_handling(tmp_path):
    """A 1-bit-per-key Bloom filter false-positives heavily; every false
    positive must degrade to a probed miss, never a wrong record."""
    idx = synth_index(2000)
    save_sharded(idx, tmp_path / "s", n_shards=4, bloom_bits_per_key=1)
    qs = IndexStore.open(tmp_path / "s")
    absent = [f"InChI=1S/absent/{i}" for i in range(2000)]
    _, _, hit = qs.lookup_batch(absent)
    assert not hit.any()
    assert qs.stats.bloom_false_positives > 0  # filter lied, probe caught it
    assert qs.stats.bloom_rejects > 0          # and it still rejects some
    # presents still all resolve (no false negatives by construction)
    keys = [f"InChI=1S/synthetic/{i}" for i in range(0, 2000, 17)]
    _, _, hit = qs.lookup_batch(keys)
    assert hit.all()


def test_incremental_save_rewrites_only_changed_shards(tmp_path):
    idx = synth_index(4000)
    root = tmp_path / "s"
    assert idx.save_sharded(root, n_shards=8)["written"] == 8
    # no change -> no rewrite
    again = idx.save_sharded(root, n_shards=8)
    assert again["written"] == 0 and again["skipped"] == 8
    # one new key -> exactly the shard owning its digest is rewritten
    new_key = "InChI=1S/synthetic/new"
    idx.add(new_key, "f_00.sdf", 999_999)
    third = idx.save_sharded(root, n_shards=8)
    assert third["written"] == 1 and third["skipped"] == 7
    qs = IndexStore.open(root)
    assert len(qs) == 4001
    assert qs.lookup(new_key) == ("f_00.sdf", 999_999)
    # different params -> full rewrite (no stale-skip across layouts)
    assert idx.save_sharded(root, n_shards=4)["written"] == 4
    # ...and the old layout's extra shard files are cleaned up, so the
    # reported storage footprint reflects the live layout only
    leftover = {p.name for p in root.glob("shard_*.npy")
                if not p.name.startswith(tuple(f"shard_000{s}" for s in range(4)))}
    assert not leftover, leftover
    # a Bloom-sizing change alone must also rewrite (the content hash only
    # covers data columns; a skipped shard would pair the old bitmap with
    # the new bloom_k -> false negatives)
    resized = idx.save_sharded(root, n_shards=4, bloom_bits_per_key=4)
    assert resized["written"] == 4 and resized["skipped"] == 0
    qs2 = IndexStore.open(root)
    keys = [f"InChI=1S/synthetic/{i}" for i in range(0, 4000, 97)]
    assert qs2.lookup_batch(keys)[2].all()


def test_republish_preserves_live_mmap_readers(tmp_path):
    """Shard rewrites go through temp-file + rename, so a reader holding a
    shard mmap'd keeps its old inode — never a torn/truncated column."""
    idx = synth_index(1000)
    root = tmp_path / "s"
    idx.save_sharded(root, n_shards=2)
    qs = IndexStore.open(root)
    keys = [f"InChI=1S/synthetic/{i}" for i in range(0, 1000, 3)]
    assert qs.lookup_batch(keys)[2].all()  # fault both shards in (mmap'd)
    before = [np.asarray(qs._shard(s).digests).copy() for s in range(2)]
    for i in range(200):
        idx.add(f"InChI=1S/more/{i}", "g.sdf", i)
    assert idx.save_sharded(root, n_shards=2)["written"] == 2
    for s in range(2):  # the live mapping still sees the old bytes, intact
        np.testing.assert_array_equal(np.asarray(qs._shard(s).digests), before[s])
    assert qs.locate_batch(keys) == [idx.lookup(k) for k in keys]
    # a fresh open serves the republished content
    assert IndexStore.open(root).lookup("InChI=1S/more/7") == ("g.sdf", 7)


def test_device_probe_parity(tmp_path):
    idx = synth_index(1500)
    idx.save_sharded(tmp_path / "s", n_shards=4)
    keys = [f"InChI=1S/synthetic/{i}" for i in range(0, 1500, 7)]
    keys += [f"InChI=1S/absent/{i}" for i in range(60)]
    host = IndexStore.open(tmp_path / "s")
    dev = IndexStore.open(tmp_path / "s")
    fh, oh, hh = host.lookup_batch(keys, probe="host")
    fd, od, hd = dev.lookup_batch(keys, probe="device")
    np.testing.assert_array_equal(hh, hd)
    np.testing.assert_array_equal(fh, fd)
    np.testing.assert_array_equal(oh, od)
    with pytest.raises(ValueError):
        host.lookup_batch(keys[:1], probe="quantum")


def test_device_probe_counts_its_upload(tmp_path):
    """The device probe copies each touched shard's whole digest column and
    the batch's queries to the device, as (hi, lo) uint32 pairs: 8 bytes a
    row and a query, counted in ``upload_bytes``, one ``device_probes``
    per touched shard.  The host probe copies nothing."""
    idx = synth_index(1500)
    idx.save_sharded(tmp_path / "s", n_shards=4)
    keys = [f"InChI=1S/synthetic/{i}" for i in range(0, 1500, 11)]
    store = IndexStore.open(tmp_path / "s")
    store.lookup_batch(keys, probe="host")
    assert (store.stats.device_probes, store.stats.upload_bytes) == (0, 0)
    store.lookup_batch(keys, probe="device")
    touched = np.unique(shard_of(digest_u64(keys), 4))
    rows = sum(int(store.manifest["shards"][int(s)]["count"]) for s in touched)
    assert store.stats.device_probes == len(touched)
    assert store.stats.upload_bytes == 8 * (rows + len(keys))


def test_device_batch_syncs_once_for_all_its_shards(tmp_path):
    """A device batch dispatches every touched shard's probe, then waits
    for all of them in one host sync: ``device_syncs`` rises by 1 a batch
    however many shards it touched, ``device_probes`` by their number."""
    idx = synth_index(1500)
    idx.save_sharded(tmp_path / "s", n_shards=8)
    keys = [f"InChI=1S/synthetic/{i}" for i in range(0, 1500, 13)]
    touched = np.unique(shard_of(digest_u64(keys), 8))
    assert len(touched) >= 3
    store = IndexStore.open(tmp_path / "s")
    rows = sum(int(store.manifest["shards"][int(s)]["count"]) for s in touched)
    store.lookup_batch(keys, probe="host")
    assert store.stats.device_syncs == 0
    for batch in (1, 2):
        store.lookup_batch(keys, probe="device")
        assert store.stats.device_syncs == batch
        assert store.stats.device_probes == batch * len(touched)
        assert store.stats.upload_bytes == batch * 8 * (rows + len(keys))
    store.lookup_batch([], probe="device")  # nothing probed, nothing to wait for
    assert store.stats.device_syncs == 2


def test_pipelined_device_probe_parity_across_collision_runs(tmp_path, monkeypatch):
    """At 12 digest bits over 4 shards every shard of one batch holds
    equal-digest runs, and some run straddles the kernel's 2,048-row table
    block, where the Pallas kernel answers a within-block position.  With
    the kernel itself (interpreted) behind ``sorted_probe``, the pipelined
    device path must resolve exactly what the host path does."""
    from repro.kernels.sorted_probe import ops
    from repro.kernels.sorted_probe.kernel import DEFAULT_TABLE_BLOCK as bt

    monkeypatch.setattr(
        ops, "sorted_probe", lambda q, t: ops.sorted_probe_pallas(q, t, interpret=True)
    )
    idx = synth_index(10000, n_files=5)
    idx.save_sharded(tmp_path / "s", n_shards=4, digest_bits=12)
    host = IndexStore.open(tmp_path / "s")
    dev = IndexStore.open(tmp_path / "s")
    straddling = [
        s for s in range(4)
        if len(d := host._shard(s).digests) > bt and d[bt - 1] == d[bt]
    ]
    assert straddling, "no collision run crosses a table block"
    keys = list(idx.entries.keys())[::3]
    for s in straddling:  # every key of each straddling run
        d, kk = host._shard(s).digests, host._shard(s).keys
        lo = int(np.searchsorted(d, d[bt], side="left"))
        hi = int(np.searchsorted(d, d[bt], side="right"))
        assert lo < bt < hi
        keys += [k.decode() for k in kk[lo:hi]]
    keys += [f"InChI=1S/absent/{i}" for i in range(300)]
    want = host.lookup_batch(keys, probe="host")
    got = dev.lookup_batch(keys, probe="device")
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert dev.stats.device_syncs == 1 and dev.stats.device_probes == 4
    assert dev.stats.verify_collisions == host.stats.verify_collisions > 0
    assert dev.stats.hits == host.stats.hits


def test_failed_dispatch_leaves_the_batch_and_the_stats(tmp_path, monkeypatch):
    """A probe that raises on the k-th dispatch of a batch propagates out of
    ``lookup_batch`` (the router scores an endpoint bug as a bug, not as a
    degraded shard) and merges none of the batch's counts."""
    import copy

    from repro.kernels.sorted_probe import ops

    idx = synth_index(1500)
    idx.save_sharded(tmp_path / "s", n_shards=8)
    keys = [f"InChI=1S/synthetic/{i}" for i in range(0, 1500, 13)]
    assert len(np.unique(shard_of(digest_u64(keys), 8))) >= 3
    store = IndexStore.open(tmp_path / "s")
    store.lookup_batch(keys, probe="device")
    before = copy.deepcopy(store.stats)
    real, calls = ops.sorted_probe, []

    def third_fails(q, t):
        calls.append(len(t))
        if len(calls) == 3:
            raise RuntimeError("probe endpoint bug")
        return real(q, t)

    monkeypatch.setattr(ops, "sorted_probe", third_fails)
    with pytest.raises(RuntimeError, match="probe endpoint bug"):
        store.lookup_batch(keys, probe="device")
    assert len(calls) == 3
    assert store.stats == before


@settings(max_examples=15)
@given(picks=st.lists(st.integers(min_value=0, max_value=2999), min_size=1,
                      max_size=60))
def test_lookup_batch_parity_hypothesis(tmp_path_factory, picks):
    global _HYP_STORE
    try:
        idx, qs = _HYP_STORE
    except NameError:
        idx = synth_index(3000)
        root = tmp_path_factory.mktemp("hyp") / "s"
        idx.save_sharded(root, n_shards=8, digest_bits=20)
        qs = IndexStore.open(root)
        _HYP_STORE = (idx, qs)
    keys = [f"InChI=1S/synthetic/{i}" for i in picks]
    keys += [f"InChI=1S/absent/{i}" for i in picks[:10]]
    assert qs.locate_batch(keys) == [idx.lookup(k) for k in keys]


def test_lookup_batch_parity_100k(tmp_path):
    """Acceptance-scale parity: ≥100k keys, digests narrowed to 24 bits so
    the corpus contains hundreds of seeded digest collisions."""
    n = 100_000
    idx = synth_index(n, n_files=31)
    digest_bits = 24
    d = digest_u64([f"InChI=1S/synthetic/{i}" for i in range(n)],
                   bits=digest_bits)
    n_colliding = int(n - len(np.unique(d)))
    assert n_colliding > 50, "collision seeding failed"
    idx.save_sharded(tmp_path / "s", n_shards=16, digest_bits=digest_bits)
    qs = IndexStore.open(tmp_path / "s")
    keys = [f"InChI=1S/synthetic/{i}" for i in range(n)]
    misses = [f"InChI=1S/absent/{i}" for i in range(2000)]
    locs = qs.locate_batch(keys + misses)
    for k, loc in zip(keys + misses, locs):
        assert loc == idx.lookup(k), k
    assert qs.stats.verify_collisions > 0


# ---------------------------------------------------------------------------
# consumers on top of the store
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    spec = CorpusSpec(n_files=2, records_per_file=150)
    root = tmp_path_factory.mktemp("corpus") / "c"
    generate_corpus(root, spec)
    return RecordStore(root), spec


def test_extract_through_index_store(corpus, tmp_path):
    store, spec = corpus
    idx = build_index(store)
    idx.save_sharded(tmp_path / "s", n_shards=4)
    qs = IndexStore.open(tmp_path / "s")
    targets = intersect_host(
        db_id_list(spec, "chembl"), db_id_list(spec, "emolecules")
    ).ids
    res_dict = extract(store, idx, targets)
    res_store = extract(store, qs, targets)
    assert res_store.records == res_dict.records
    assert res_store.missing == res_dict.missing
    assert not res_store.mismatches


def test_indexed_dataset_on_index_store(corpus, tmp_path):
    from repro.data.pipeline import IndexedDataset
    from repro.data.sampler import GlobalSampler

    store, spec = corpus
    idx = build_index(store)
    idx.save_sharded(tmp_path / "s", n_shards=4)
    qs = IndexStore.open(tmp_path / "s")
    ds_dict = IndexedDataset(store, idx, seq_len=64)
    ds_store = IndexedDataset(store, qs, seq_len=64)
    assert ds_store.keys == ds_dict.keys  # same deterministic ordering
    sampler = GlobalSampler(n_examples=len(ds_store), global_batch=4, seed=0)
    a = ds_dict.batch_for(sampler, step=3, dp_rank=0, n_dp=1)
    b = ds_store.batch_for(sampler, step=3, dp_rank=0, n_dp=1)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_array_equal(a["loss_mask"], b["loss_mask"])


# ---------------------------------------------------------------------------
# intersect: shared helpers + intra-table collision-run fix
# ---------------------------------------------------------------------------

def test_candidate_runs_cover_equal_digest_spans():
    table = np.array([1, 3, 3, 3, 7], dtype=np.uint64)
    starts, stops = candidate_runs(table, np.array([0, 3, 7, 9], dtype=np.uint64))
    assert list(starts) == [0, 1, 4, 5]
    assert list(stops) == [0, 4, 5, 5]


def test_intersect_sorted_survives_intra_table_collisions():
    """At 8 digest bits distinct ids collide constantly inside the running
    table; side='left' alone verified only the first of each equal-digest
    run and dropped true members behind it."""
    a = [f"InChI=1S/x/{i}" for i in range(400)]
    b = [f"InChI=1S/x/{i}" for i in range(0, 400, 2)]
    c = [f"InChI=1S/x/{i}" for i in range(0, 400, 3)]
    want = intersect_host(a, b, c).ids
    got = intersect_sorted(a, b, c, digest_bits=8)
    assert got.ids == want
    # default width unchanged and still exact
    assert intersect_sorted(a, b, c).ids == want

# ---------------------------------------------------------------------------
# concurrency: cold-store thread safety + the pinned serving plane
# ---------------------------------------------------------------------------

def test_cold_store_survives_concurrent_first_touch(tmp_path):
    """Many threads hammering a COLD store race the lazy shard/Bloom
    np.load (the scatter-gather workers' access pattern); every thread
    must see correct results and no partially-initialized shard."""
    import threading

    idx = synth_index(6000, n_files=5)
    idx.save_sharded(tmp_path / "s", n_shards=16)
    qs = IndexStore.open(tmp_path / "s")  # cold: nothing loaded yet
    keys = list(idx.entries.keys())
    absent = [f"InChI=1S/absent/{i}" for i in range(200)]
    want = {k: idx.lookup(k) for k in keys}
    errors = []

    def hammer(seed: int) -> None:
        try:
            mine = keys[seed::12] + absent[seed::12]
            locs = qs.locate_batch(mine)
            for k, loc in zip(mine, locs):
                assert loc == want.get(k), (k, loc)
        except Exception as e:  # pragma: no cover - the regression signal
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert qs.shards_loaded == 16
    assert qs.stats.queries == len(keys) + len(absent)  # no lost updates


def test_serving_plane_parity_with_collisions(tmp_path):
    """The pinned digest/file/offset plane must return exactly what the
    per-shard probe returns — including collision runs at truncated
    digest widths — with identical stats."""
    idx = synth_index(9000, n_files=5)
    idx.save_sharded(tmp_path / "s", n_shards=8, digest_bits=16)
    plain = IndexStore.open(tmp_path / "s")
    plane = IndexStore.open(tmp_path / "s")
    planes = plane.preload_digest_plane()
    keys = list(idx.entries.keys())[::2] + [
        f"InChI=1S/absent/{i}" for i in range(500)
    ]
    want = plain.lookup_batch(keys)
    got = plane.lookup_batch(keys)
    for w, g in zip(want, got):
        assert (w == g).all()
    assert plain.stats.verify_collisions == plane.stats.verify_collisions
    assert plain.stats.verify_collisions > 0  # 16-bit digests do collide
    assert plain.stats.bloom_rejects == plane.stats.bloom_rejects
    assert plain.stats.hits == plane.stats.hits
    assert plain.stats.shards_touched == plane.stats.shards_touched
    # adopt_planes shares the (read-only) planes across replicas
    third = IndexStore.open(tmp_path / "s")
    third.adopt_planes(planes)
    got3 = third.lookup_batch(keys)
    for w, g in zip(want, got3):
        assert (w == g).all()
