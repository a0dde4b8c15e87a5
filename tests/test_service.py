"""Query-service tests: scheduler flush ordering and cancellation, router
scatter-gather merges, and QueryService fetch parity (byte-identical vs
the direct serial ``extract``) on a collision-seeded corpus.
"""

import tempfile
import threading
import time
from concurrent.futures import CancelledError
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
from repro.core.sdfgen import CorpusSpec, db_id_list, generate_corpus
from repro.data.pipeline import IndexedDataset
from repro.service import (
    MicroBatcher,
    QueryService,
    ServiceConfig,
    ShardRouter,
    run_closed_loop,
)

KEY_BITS = 16  # collision-prone at corpus scale: mismatch path exercised


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def corpus():
    spec = CorpusSpec(n_files=3, records_per_file=500, key_bits=KEY_BITS)
    root = Path(tempfile.mkdtemp()) / "corpus"
    generate_corpus(root, spec)
    return RecordStore(root), spec


@pytest.fixture(scope="module")
def targets(corpus):
    _, spec = corpus
    return intersect_host(
        db_id_list(spec, "chembl", extra_outside=15),
        db_id_list(spec, "emolecules", extra_outside=15),
    ).ids


@pytest.fixture(scope="module")
def hashed_store_dir(corpus):
    """Collision-seeded hashed-key index published as a sharded store."""
    store, _ = corpus
    idx = build_index(store, key_mode="hashed_key", key_bits=KEY_BITS)
    assert idx.stats.n_duplicate_keys > 0
    sdir = Path(tempfile.mkdtemp()) / "istore_hashed"
    idx.save_sharded(sdir, n_shards=8)
    return sdir


@pytest.fixture(scope="module")
def full_store_dir(corpus):
    store, _ = corpus
    idx = build_index(store, key_mode="full_id")
    sdir = Path(tempfile.mkdtemp()) / "istore_full"
    idx.save_sharded(sdir, n_shards=8)
    return sdir


def _fake_probe(keys):
    """Deterministic fake backend: encodes each key's int suffix."""
    vals = np.array([int(k.rsplit("/", 1)[1]) for k in keys], dtype=np.int64)
    return vals.astype(np.int32), vals * 10, np.ones(len(keys), dtype=bool)


# ---------------------------------------------------------------------------
# MicroBatcher: flush ordering, mapping, cancellation, shutdown
# ---------------------------------------------------------------------------

def _blocked_batcher(max_batch=8, max_wait_ms=10_000.0):
    """Batcher whose first probe blocks until ``release`` is set — lets a
    test pile requests into the admission queue deterministically."""
    release = threading.Event()
    probing = threading.Event()
    calls = []

    def probe(keys):
        calls.append(list(keys))
        if len(calls) == 1:
            probing.set()
            assert release.wait(10)
        return _fake_probe(keys)

    return MicroBatcher(probe, max_batch=max_batch, max_wait_ms=max_wait_ms), \
        release, probing, calls


def test_full_batch_flush_and_result_mapping():
    """Requests queued behind a slow probe merge into one full-batch flush,
    and every future gets exactly its own rows."""
    mb, release, probing, calls = _blocked_batcher(max_batch=8)
    t = threading.Thread(target=lambda: mb.lookup(["k/0"]))
    t.start()
    assert probing.wait(10)  # leader is stuck inside probe #1
    futs = [mb.submit([f"k/{i}", f"k/{100 + i}"]) for i in range(1, 5)]
    release.set()
    t.join(10)
    for i, fut in enumerate(futs, start=1):
        fid, off, hit = fut.result(timeout=10)
        assert fid.tolist() == [i, 100 + i]
        assert off.tolist() == [i * 10, (100 + i) * 10]
        assert hit.all()
    mb.close()
    # probe #1 carried the solo leader; the queued 4 requests (8 keys)
    # flushed as ONE full batch, in submission order
    assert calls[0] == ["k/0"]
    assert calls[1] == [f"k/{i}" if j == 0 else f"k/{100 + i}"
                       for i in range(1, 5) for j in (0, 1)]
    assert mb.stats.full_flushes == 1
    assert mb.stats.coalesced_batches == 1
    assert mb.stats.coalesced_requests == 4
    assert mb.stats.batch_keys_max == 8


def test_max_batch_splits_queued_requests():
    """More queued keys than max_batch: whole requests split across
    consecutive flushes, never mid-request."""
    mb, release, probing, calls = _blocked_batcher(max_batch=4)
    t = threading.Thread(target=lambda: mb.lookup(["k/0"]))
    t.start()
    assert probing.wait(10)
    futs = [mb.submit([f"k/{i}", f"k/{100 + i}"]) for i in range(1, 5)]
    release.set()
    for fut in futs:
        fut.result(timeout=10)
    t.join(10)
    mb.close()
    assert [len(c) for c in calls] == [1, 4, 4]  # 2+2 keys per flush
    assert mb.stats.full_flushes >= 1


def test_deadline_flush_fires_without_new_arrivals():
    """A lone request below the armed cohort target is flushed by the
    watchdog at the max_wait deadline, not stuck forever."""
    mb, release, probing, _ = _blocked_batcher(max_batch=64, max_wait_ms=25.0)
    # phase 1: force a coalesced batch so the batcher enters cohort mode
    t = threading.Thread(target=lambda: mb.lookup(["k/0"]))
    t.start()
    assert probing.wait(10)
    f1, f2 = mb.submit(["k/1"]), mb.submit(["k/2"])
    release.set()
    f1.result(10), f2.result(10)
    t.join(10)
    assert mb.stats.coalesced_batches == 1
    assert mb._coalescing
    # phase 2: one below-target request arms and must deadline-flush
    t0 = time.monotonic()
    fid, _off, hit = mb.lookup(["k/7"], timeout=10)
    dt = time.monotonic() - t0
    assert fid.tolist() == [7] and hit.all()
    assert mb.stats.deadline_flushes >= 1
    assert dt >= 0.015  # it actually waited toward the deadline
    mb.close()


def test_cohort_flush_fires_on_target_arrival():
    """Concurrent closed-loop clients trigger cohort flushes (the armed
    target re-forms) and the latency window fills."""
    mb = MicroBatcher(_fake_probe, max_batch=64, max_wait_ms=50.0)
    keys = [f"k/{i}" for i in range(64)]
    rep = run_closed_loop(
        lambda ks: mb.lookup(ks), keys, clients=6, duration_s=0.4
    )
    assert rep.errors == 0
    assert mb.stats.coalesced_batches > 0
    assert mb.stats.cohort_flushes > 0
    assert mb.stats.mean_batch_keys > 1.0
    lat = mb.latency_ms()
    assert lat["p50"] > 0 and lat["p99"] >= lat["p50"]
    mb.close()


def test_queue_wait_is_counted_once_per_flushed_request():
    """Each flushed request adds (flush - submit) to ``queue_wait_s``;
    ``latency_ms()["mean_wait"]`` is that sum over flushed requests."""
    mb, release, probing, calls = _blocked_batcher(max_batch=8)
    t = threading.Thread(target=lambda: mb.lookup(["k/0"]))
    t.start()
    assert probing.wait(10)
    futs = [mb.submit([f"k/{i}"]) for i in range(1, 4)]
    time.sleep(0.05)            # the three wait behind the blocked probe
    release.set()
    t.join(10)
    for f in futs:
        f.result(timeout=10)
    mb.close()
    st = mb.stats
    assert st.requests_flushed == 4 and st.batches == 2
    assert st.queue_wait_s >= 3 * 0.05
    assert mb.latency_ms()["mean_wait"] == pytest.approx(
        st.queue_wait_s / 4 * 1e3)
    assert not hasattr(mb, "wait_seconds")


def test_shutdown_cancels_queued_futures():
    mb, release, probing, calls = _blocked_batcher()
    t = threading.Thread(target=lambda: mb.lookup(["k/0"]))
    t.start()
    assert probing.wait(10)
    queued = mb.submit(["k/9"])
    closer = threading.Thread(target=mb.close)  # drain=False: cancel
    closer.start()
    release.set()
    t.join(10)
    closer.join(10)
    assert queued.cancelled()
    with pytest.raises(CancelledError):
        queued.result(timeout=1)
    assert mb.stats.cancelled >= 1
    assert all("k/9" not in c for c in calls)  # never probed
    with pytest.raises(RuntimeError):
        mb.submit(["k/10"])  # closed


def test_close_drain_probes_queued_requests():
    mb, release, probing, _ = _blocked_batcher()
    t = threading.Thread(target=lambda: mb.lookup(["k/0"]))
    t.start()
    assert probing.wait(10)
    queued = mb.submit(["k/9"])
    closer = threading.Thread(target=lambda: mb.close(drain=True))
    closer.start()
    release.set()
    t.join(10)
    closer.join(10)
    fid, _off, hit = queued.result(timeout=1)
    assert fid.tolist() == [9] and hit.all()


def test_cancelled_future_withdraws_request():
    mb, release, probing, calls = _blocked_batcher()
    t = threading.Thread(target=lambda: mb.lookup(["k/0"]))
    t.start()
    assert probing.wait(10)
    doomed = mb.submit(["k/5"])
    kept = mb.submit(["k/6"])
    assert doomed.cancel()
    release.set()
    t.join(10)
    assert kept.result(10)[0].tolist() == [6]
    mb.close()
    assert all("k/5" not in c for c in calls)
    assert mb.stats.cancelled >= 1


def test_probe_exception_propagates_to_every_future():
    def bad_probe(keys):
        raise RuntimeError("shard on fire")

    mb = MicroBatcher(bad_probe, max_wait_ms=5.0)
    with pytest.raises(RuntimeError, match="shard on fire"):
        mb.lookup(["k/0"], timeout=5)
    mb.close()


def test_batcher_validates_knobs():
    with pytest.raises(ValueError):
        MicroBatcher(_fake_probe, max_batch=0)
    with pytest.raises(ValueError):
        MicroBatcher(_fake_probe, max_wait_ms=-1)


# ---------------------------------------------------------------------------
# ShardRouter: scatter-gather merge parity + stats
# ---------------------------------------------------------------------------

def test_router_matches_direct_store(full_store_dir, corpus):
    store, _ = corpus
    direct = IndexStore.open(full_store_dir)
    keys = sorted(direct.iter_keys())
    probe_keys = keys[::3] + [f"InChI=1S/absent/{i}" for i in range(40)]
    want = direct.lookup_batch(probe_keys)
    # min_scatter_keys=1 forces the scatter path; replicas checkout works
    with ShardRouter(full_store_dir, replicas=3, min_scatter_keys=1) as router:
        got = router.lookup_batch(probe_keys)
        for w, g in zip(want, got):
            assert (w == g).all()
        assert router.stats.scattered >= 1
        assert router.stats.shard_probes > 1
        assert sum(router.stats.keys_per_shard.values()) == len(probe_keys)
        qs = router.query_stats()
        assert qs.queries == len(probe_keys)
        assert qs.hits == int(want[2].sum())
        # locate surface mirrors the store's
        assert router.locate_batch(probe_keys[:5]) == direct.locate_batch(
            probe_keys[:5]
        )
        assert router.lookup(probe_keys[0]) == direct.lookup(probe_keys[0])
    with pytest.raises(RuntimeError):
        router.lookup_batch(probe_keys[:2])  # closed


def test_router_inline_path_small_batches(full_store_dir):
    direct = IndexStore.open(full_store_dir)
    keys = sorted(direct.iter_keys())[:10]
    router = ShardRouter(full_store_dir, replicas=2, min_scatter_keys=1024)
    got = router.lookup_batch(keys)
    want = direct.lookup_batch(keys)
    for w, g in zip(want, got):
        assert (w == g).all()
    assert router.stats.inline == 1 and router.stats.scattered == 0
    empty = router.lookup_batch([])
    assert all(len(a) == 0 for a in empty)
    router.close()


def test_router_rejects_bad_replicas(full_store_dir):
    with pytest.raises(ValueError):
        ShardRouter(full_store_dir, replicas=0)


# ---------------------------------------------------------------------------
# QueryService: byte parity vs the serial reference (the stats-parity gate)
# ---------------------------------------------------------------------------

def test_service_fetch_parity_on_collision_seeded_corpus(
    corpus, targets, hashed_store_dir
):
    """Service-path fetch must reproduce the serial loop byte-for-byte:
    records (content AND order), missing, and the collision mismatches."""
    store, _ = corpus
    idx = build_index(store, key_mode="hashed_key", key_bits=KEY_BITS)
    serial = extract(store, idx, targets, key_bits=KEY_BITS, workers=0)
    assert serial.mismatches and serial.missing  # both paths exercised
    with QueryService(store, hashed_store_dir, ServiceConfig(replicas=2)) as svc:
        res = svc.fetch(targets, key_bits=KEY_BITS)
        assert list(res.records.items()) == list(serial.records.items())
        assert res.missing == serial.missing
        assert res.mismatches == serial.mismatches
        # warm pass: served from the shared cache, still byte-identical
        res2 = svc.fetch(targets, key_bits=KEY_BITS)
        assert list(res2.records.items()) == list(serial.records.items())
        assert res2.cache_hits == res2.seeks
        assert res2.spans_read == 0


def test_service_concurrent_fetches_stay_identical(
    corpus, targets, hashed_store_dir
):
    store, _ = corpus
    idx = build_index(store, key_mode="hashed_key", key_bits=KEY_BITS)
    serial = extract(store, idx, targets, key_bits=KEY_BITS, workers=0)
    with QueryService(store, hashed_store_dir, ServiceConfig(replicas=2)) as svc:
        outs = {}

        def worker(i):
            outs[i] = svc.fetch(targets, key_bits=KEY_BITS)

        ths = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        for res in outs.values():
            assert list(res.records.items()) == list(serial.records.items())
            assert res.missing == serial.missing
            assert res.mismatches == serial.mismatches


def test_service_fetch_stream_and_lookup(corpus, targets, full_store_dir):
    store, _ = corpus
    idx = build_index(store, key_mode="full_id")
    serial = extract(store, idx, targets, workers=0)
    with QueryService(store, full_store_dir) as svc:
        got = dict(svc.fetch_stream(targets))
        assert got == serial.records
        # lookup surface: present and absent keys
        present = list(serial.records.keys())[:5]
        locs = svc.lookup(present + ["InChI=1S/absent/0"])
        assert all(loc is not None for loc in locs[:5])
        assert locs[-1] is None
        assert locs[:5] == [idx.lookup(k) for k in present]
        assert present[0] in svc and "InChI=1S/absent/0" not in svc
        assert len(svc) == len(idx)


def test_service_stats_counters(corpus, targets, full_store_dir):
    store, _ = corpus
    with QueryService(store, full_store_dir, ServiceConfig(replicas=2)) as svc:
        svc.fetch(targets)
        lk = sorted(svc.router.iter_keys())[:300]

        def looker(i):
            for j in range(i, len(lk), 6):
                svc.lookup_batch(lk[j:j + 3])

        ths = [threading.Thread(target=looker, args=(i,)) for i in range(6)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        s = svc.stats()
        assert s["scheduler"]["requests"] > 0
        assert s["scheduler"]["coalesced_batches"] > 0
        assert s["scheduler"]["mean_batch_keys"] > 1.0
        assert s["store"]["queries"] == s["router"]["keys"]
        assert s["cache"]["entries"] > 0
        assert s["read"]["records"] > 0
        assert s["scheduler"]["latency_ms"]["p99"] >= \
            s["scheduler"]["latency_ms"]["p50"]


def test_indexed_dataset_rides_the_service(corpus, full_store_dir):
    store, _ = corpus
    idx = build_index(store, key_mode="full_id")
    direct = IndexedDataset(store, idx, seq_len=64, cache_records=512)
    with QueryService(store, full_store_dir) as svc:
        ds = IndexedDataset(store, None, seq_len=64, service=svc)
        assert ds.keys == direct.keys
        sample = ds.keys[:40]
        assert ds.fetch_many(list(sample)) == direct.fetch_many(list(sample))
        assert ds.fetch_record(sample[0]) == direct.fetch_record(sample[0])
        with pytest.raises(KeyError):
            ds.fetch_many(["InChI=1S/absent/0"])
    with pytest.raises(ValueError):
        IndexedDataset(store, None, seq_len=64)  # no index, no service


# ---------------------------------------------------------------------------
# load generator
# ---------------------------------------------------------------------------

def test_run_closed_loop_accounting():
    calls = []

    def fn(ks):
        calls.append(len(ks))

    rep = run_closed_loop(fn, ["a", "b", "c"], clients=3, duration_s=0.2,
                          keys_per_request=2)
    assert rep.requests == len(calls)
    assert rep.keys == 2 * rep.requests
    assert rep.lookups_per_sec > 0
    assert rep.p99_ms >= rep.p50_ms >= 0
    assert set(calls) == {2}
    with pytest.raises(ValueError):
        run_closed_loop(fn, [], clients=1)
    with pytest.raises(ValueError):
        run_closed_loop(fn, ["a"], clients=0)
