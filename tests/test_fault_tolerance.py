"""Fault-tolerance tests: heartbeat publish, failure-detector edges,
micro-batcher leader-death containment, fault-injecting transports,
health tracking, and the router's failover / hedged / degraded serving.

The acceptance gate lives here too: a seeded chaos run (one dead shard +
one slow shard) must produce byte-identical degraded results across two
runs, fire hedged requests, and return byte-identical clean results
after revival.
"""

import json
import os
import tempfile
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import repro.runtime.fault as fault_mod
from repro.core import RecordStore, build_index
from repro.core.sdfgen import CorpusSpec, generate_corpus
from repro.core.store import IndexStore, digest_u64, merge_similar_topk, shard_of
from repro.runtime.fault import (
    BackoffPolicy,
    ElasticPlan,
    FailureDetector,
    Heartbeat,
    run_with_failures,
)
from repro.service import (
    DEAD,
    DEGRADED,
    UP,
    FaultInjectingTransport,
    FlakyError,
    HealthTracker,
    LocalTransport,
    MicroBatcher,
    ProbeTimeoutError,
    QueryService,
    ServiceConfig,
    ShardDownError,
    ShardRouter,
    run_closed_loop,
)
from repro.service.transport import error_kind


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def corpus():
    spec = CorpusSpec(n_files=2, records_per_file=300)
    root = Path(tempfile.mkdtemp()) / "corpus"
    generate_corpus(root, spec)
    return RecordStore(root), spec


@pytest.fixture(scope="module")
def store_dir(corpus):
    store, _ = corpus
    idx = build_index(store, key_mode="full_id")
    sdir = Path(tempfile.mkdtemp()) / "istore"
    idx.save_sharded(sdir, n_shards=8, fingerprint_bits=256)
    return sdir


@pytest.fixture(scope="module")
def probe_keys(store_dir):
    st = IndexStore.open(store_dir)
    return sorted(st.iter_keys())[:240]


def _chaos_router(store_dir, seed=42, **kw):
    """Router over fault-injecting transports; returns (router, injectors)."""
    injectors = []

    def factory(st, i):
        tr = FaultInjectingTransport(
            LocalTransport(st, name=f"r{i}"), seed=seed + i
        )
        injectors.append(tr)
        return tr

    kw.setdefault("replicas", 2)
    kw.setdefault("min_scatter_keys", 1)
    kw.setdefault("probe_timeout_ms", 250.0)
    kw.setdefault("fail_threshold", 1)
    kw.setdefault("health_backoff", BackoffPolicy(base_s=0.1, cap_s=0.5))
    rt = ShardRouter(store_dir, transport_factory=factory, **kw)
    return rt, injectors


# ---------------------------------------------------------------------------
# satellite: Heartbeat tmp-file publish
# ---------------------------------------------------------------------------

def test_heartbeat_tmp_name_survives_dots_and_carries_pid(tmp_path):
    """Regression: ``with_suffix`` rewrites everything after the last dot
    of the final component, so a dotted heartbeat file name collapsed to
    a shared ``hb.tmp`` — racing ranks then interleaved publishes."""
    hb = Heartbeat(tmp_path, 3)
    hb.path = tmp_path / "hb.v2_00003"  # dotted name: the mangling case
    hb.beat(step=7)
    assert json.loads(hb.path.read_text())["step"] == 7
    # nothing else left behind, and the tmp path never clobbered a sibling
    assert sorted(p.name for p in tmp_path.iterdir()) == ["hb.v2_00003"]
    # the tmp naming is per-pid and per-thread, so neither sibling
    # processes nor pool threads beating one rank can interleave writes
    # into a single tmp file
    tmp = hb.path.with_name(
        f"{hb.path.name}.{os.getpid()}.{threading.get_ident()}.tmp"
    )
    assert str(os.getpid()) in tmp.name


def test_heartbeat_concurrent_beats_stay_atomic(tmp_path):
    hb = Heartbeat(tmp_path, 0)
    stop = threading.Event()
    errors = []

    def beater(base):
        i = 0
        while not stop.is_set():
            try:
                hb.beat(step=base + i)
            except Exception as e:  # pragma: no cover
                errors.append(e)
            i += 1

    threads = [
        threading.Thread(target=beater, args=(t * 10_000,))
        for t in range(4)
    ]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 0.5
    while time.monotonic() < deadline:
        got = hb.read()  # every observed publish is complete JSON
        assert got is None or "step" in got
    stop.set()
    for t in threads:
        t.join(5)
    assert not errors
    assert hb.read() is not None
    assert [p.name for p in tmp_path.glob("*.tmp")] == []


def test_heartbeat_cleans_tmp_on_write_failure(tmp_path, monkeypatch):
    hb = Heartbeat(tmp_path, 1)
    real_replace = os.replace

    def boom(src, dst):
        raise OSError("disk full")

    monkeypatch.setattr(fault_mod.os, "replace", boom)
    with pytest.raises(OSError):
        hb.beat(step=1)
    monkeypatch.setattr(fault_mod.os, "replace", real_replace)
    assert [p.name for p in tmp_path.glob("*.tmp")] == []


# ---------------------------------------------------------------------------
# satellite: FailureDetector / ElasticPlan / run_with_failures edges
# ---------------------------------------------------------------------------

def test_failure_detector_boundary_and_clock_skew(tmp_path, monkeypatch):
    det = FailureDetector(tmp_path, n_workers=3, timeout=5.0)
    now = 1_000_000.0
    monkeypatch.setattr(fault_mod.time, "time", lambda: now)
    # rank 0: exactly at the timeout boundary — still alive (<=)
    (tmp_path / "hb_00000").write_text(json.dumps({"step": 1, "t": now - 5.0}))
    # rank 1: a hair past the deadline — dead
    (tmp_path / "hb_00001").write_text(
        json.dumps({"step": 1, "t": now - 5.0001})
    )
    # rank 2: heartbeat from the future (clock skew) — alive, not dead
    (tmp_path / "hb_00002").write_text(json.dumps({"step": 1, "t": now + 60}))
    assert det.alive() == [0, 2]
    assert det.dead() == [1]


def test_elastic_plan_zero_survivors_raises():
    with pytest.raises(RuntimeError, match="no survivors"):
        ElasticPlan.for_survivors(0, n_model=2)
    assert ElasticPlan.for_survivors(3, n_model=2).n_dp == 3


def test_run_with_failures_failure_at_step_zero():
    """A failure scheduled before any training ran must shrink dp BEFORE
    the first chunk launches (regression: it was silently ignored)."""
    seen = []

    def chunk(start, until, n_dp):
        seen.append((start, until, n_dp))
        return until, {}

    log = run_with_failures(
        total_steps=8, train_chunk=chunk, fail_at={0: 2}, initial_dp=4
    )
    assert seen == [(0, 8, 2)]
    kinds = [e["kind"] for e in log.events]
    assert kinds == ["failure", "chunk"]
    assert log.events[0]["new_dp"] == 2


# ---------------------------------------------------------------------------
# satellite: MicroBatcher leader-death containment
# ---------------------------------------------------------------------------

def test_batcher_systemexit_delivered_and_followers_rescued():
    """A probe raising SystemExit kills its leader (client) thread, but
    the batch's futures get the exception and later requests are rescued
    by the watchdog sweep instead of waiting forever."""
    calls = []

    def probe(keys):
        calls.append(list(keys))
        if len(calls) == 1:
            raise SystemExit("poisoned probe")
        v = np.arange(len(keys))
        return v.astype(np.int32), v.astype(np.int64) * 10, np.ones(
            len(keys), dtype=bool
        )

    mb = MicroBatcher(probe, max_batch=8, max_wait_ms=5.0)
    first_exc = []

    def doomed_client():
        try:
            mb.lookup(["k/1"])
        except BaseException as e:  # noqa: BLE001
            first_exc.append(e)

    t = threading.Thread(target=doomed_client)
    t.start()
    t.join(5)
    assert not t.is_alive()
    assert isinstance(first_exc[0], SystemExit)
    # no live leader now; the watchdog's periodic sweep must lead this
    out = mb.submit(["k/2"]).result(timeout=5)
    assert len(out) == 3 and bool(out[2][0])
    mb.close()


def test_batcher_close_bounded_by_grace_when_leader_wedged():
    """close(drain=False) must not block forever behind a probe that
    never returns: pending requests cancel, close returns within the
    grace window, the wedged cohort's futures stay pending."""
    wedge = threading.Event()

    def probe(keys):
        wedge.wait(30)
        v = np.arange(len(keys))
        return v.astype(np.int32), v.astype(np.int64), np.ones(
            len(keys), dtype=bool
        )

    mb = MicroBatcher(probe, close_grace_s=0.2)
    inflight_res = []
    th = threading.Thread(
        target=lambda: inflight_res.append(mb.submit(["k/1"]).result(35)),
        daemon=True,
    )
    th.start()
    time.sleep(0.15)  # let the leader enter the wedged probe
    queued = mb.submit(["k/2"])
    t0 = time.monotonic()
    mb.close(drain=False)
    assert time.monotonic() - t0 < 2.0
    assert queued.cancelled()
    assert mb.stats.cancelled >= 1
    wedge.set()  # un-wedge: the alive leader still resolves its cohort
    th.join(5)
    assert inflight_res and len(inflight_res[0]) == 3


def test_batcher_close_recovers_cohort_of_dead_leader():
    """White-box: a leader thread that died without unwinding (no Python
    exception reached _execute's handler) leaves its cohort unresolved —
    close() must deliver a RuntimeError rather than hang the callers."""
    mb = MicroBatcher(lambda keys: None, close_grace_s=0.1)
    from repro.service.scheduler import _Request

    req = _Request(["k/1"])
    assert req.future.set_running_or_notify_cancel()
    dead = threading.Thread(target=lambda: None)
    dead.start()
    dead.join()
    with mb._lock:
        mb._inflight = [req]
        mb._leader_thread = dead
    assert mb._leader.acquire(blocking=False)  # simulate a held flush
    try:
        mb.close(drain=False)
    finally:
        mb._leader.release()
    with pytest.raises(RuntimeError, match="leader died mid-flush"):
        req.future.result(timeout=1)
    assert mb.stats.leader_deaths == 1


def test_batcher_slices_extra_columns_and_preserves_type():
    from repro.service import LookupBatchResult

    def probe(keys):
        n = len(keys)
        return LookupBatchResult(
            np.arange(n, dtype=np.int32),
            np.arange(n, dtype=np.int64) * 10,
            np.ones(n, dtype=bool),
            np.array([k.endswith("dead") for k in keys]),
        )

    mb = MicroBatcher(probe)
    out = mb.lookup(["a", "b/dead"])
    assert isinstance(out, LookupBatchResult)
    assert out.degraded.tolist() == [False, True]
    mb.close()


# ---------------------------------------------------------------------------
# FaultInjectingTransport
# ---------------------------------------------------------------------------

def test_transport_kill_revive_and_taxonomy(store_dir, probe_keys):
    st = IndexStore.open(store_dir)
    tr = FaultInjectingTransport(LocalTransport(st), seed=1)
    keys = probe_keys[:20]
    dg = digest_u64(keys)
    shards = np.unique(shard_of(dg, st.n_shards, st.digest_bits)).tolist()
    s = shards[0]
    tr.kill(shard=s)
    with pytest.raises(ShardDownError) as ei:
        tr.lookup_shard(s, keys, dg)
    assert error_kind(ei.value) == "down" and ei.value.shard == s
    assert tr.injected["down"] == 1
    # whole-batch probes inherit the worst state of the shards they touch
    with pytest.raises(ShardDownError):
        tr.lookup_all(keys, dg)
    tr.revive(shard=s)
    fid, off, hit = tr.lookup_all(keys, dg)
    assert hit.all()

    tr.set_latency(50.0, shard=s)  # delay >= deadline -> timeout error
    with pytest.raises(ProbeTimeoutError) as ei:
        tr.lookup_shard(s, keys, dg, timeout_s=0.02)
    assert error_kind(ei.value) == "timeout"
    tr.clear()

    tr.set_error_rate(1.0, shard=s)
    with pytest.raises(FlakyError) as ei:
        tr.lookup_shard(s, keys, dg)
    assert error_kind(ei.value) == "error"
    tr.clear()
    assert tr.lookup_shard(s, keys, dg)[2].all()


def test_transport_fault_sequence_is_seed_deterministic(store_dir, probe_keys):
    """Same seed + same probe sequence => same injected fault sequence,
    regardless of wall clock (per-shard RNG streams)."""
    st = IndexStore.open(store_dir)
    keys = probe_keys[:30]
    dg = digest_u64(keys)
    s = int(shard_of(dg, st.n_shards, st.digest_bits)[0])

    def run_seq(seed):
        tr = FaultInjectingTransport(LocalTransport(st), seed=seed)
        tr.set_error_rate(0.5, shard=s)
        outcomes = []
        for _ in range(24):
            try:
                tr.lookup_shard(s, keys[:4], dg[:4])
                outcomes.append("ok")
            except FlakyError:
                outcomes.append("flaky")
        return outcomes

    a, b, c = run_seq(7), run_seq(7), run_seq(8)
    assert a == b
    assert "flaky" in a and "ok" in a
    assert a != c  # different seed, different stream (overwhelmingly)


# ---------------------------------------------------------------------------
# HealthTracker
# ---------------------------------------------------------------------------

def test_health_state_machine_and_probation_pacing():
    t = [0.0]
    h = HealthTracker(
        n_replicas=2, fail_threshold=2,
        backoff=BackoffPolicy(base_s=1.0, multiplier=2.0, cap_s=8.0),
        clock=lambda: t[0],
    )
    assert h.state(0, 3) == UP and not h.has_unhealthy()
    h.on_failure(0, 3, "down")
    assert h.state(0, 3) == DEGRADED and h.has_unhealthy()
    h.on_failure(0, 3, "down")
    assert h.state(0, 3) == DEAD
    # dead replica excluded while inside the backoff window
    assert h.candidates(3) == [1]
    t[0] = 1.5  # past base_s: exactly one probation probe handed out
    assert h.candidates(3) == [1, 0]
    assert h.candidates(3) == [1]  # window advanced: no stampede
    # failed probation widens the window exponentially
    h.on_failure(0, 3, "down")
    t[0] = 3.0
    assert h.candidates(3) == [1]          # 1.5 + 2.0 = 3.5 not reached
    t[0] = 4.0
    assert h.candidates(3) == [1, 0]
    # successful probation revives and records the recovery time
    h.on_success(0, 3, latency_s=0.01)
    assert h.state(0, 3) == UP
    snap = h.snapshot()
    assert snap["revivals"] == 1
    assert snap["last_recovery_s"] == pytest.approx(4.0 - 0.0)
    assert snap["failures"]["down"] == 3


def test_health_p95_and_snapshot_taxonomy():
    h = HealthTracker(n_replicas=1)
    assert h.p95_s(0, 0) is None
    for ms in range(1, 101):
        h.on_success(0, 0, latency_s=ms / 1e3)
    assert h.p95_s(0, 0) == pytest.approx(0.095, abs=0.005)
    h.on_failure(0, 1, "timeout")
    snap = h.snapshot()
    assert snap["replica_state"] == [DEGRADED]
    assert snap["failures"] == {"timeout": 1}


def test_health_heartbeats_feed_failure_detector(tmp_path):
    h = HealthTracker(n_replicas=2, rundir=tmp_path, heartbeat_interval_s=0.0)
    h.on_success(0, 0, 0.001)
    h.on_success(1, 0, 0.001)
    snap = h.snapshot()
    assert snap["heartbeat_alive"] == [0, 1]
    assert sorted(p.name for p in tmp_path.glob("hb_*")) == [
        "hb_00000", "hb_00001"
    ]


# ---------------------------------------------------------------------------
# router failover / hedging / degraded mode
# ---------------------------------------------------------------------------

def test_router_fails_over_to_sibling_replica(store_dir, probe_keys):
    with ShardRouter(store_dir, replicas=2, min_scatter_keys=1) as clean:
        want = clean.lookup_batch(probe_keys)
    rt, inj = _chaos_router(store_dir)
    try:
        dead_shard = 2
        inj[0].kill(shard=dead_shard)  # one replica only: siblings cover
        res = rt.lookup_batch_ex(probe_keys)
        assert not res.degraded.any()
        for got, ref in zip((res.file_ids, res.offsets, res.hit), want):
            assert np.array_equal(got, ref)
        assert rt.stats.retries >= 1
        assert rt.stats.errors_per_shard[dead_shard]["down"] >= 1
        assert rt.health.state(0, dead_shard) == DEAD
        assert rt.health.state(1, dead_shard) == UP
    finally:
        rt.close()


def test_router_degraded_mask_matches_dead_shard(store_dir, probe_keys):
    rt, inj = _chaos_router(store_dir)
    try:
        dead_shard = 1
        for tr in inj:
            tr.kill(shard=dead_shard)
        res = rt.lookup_batch_ex(probe_keys)
        sid = shard_of(
            digest_u64(probe_keys, bits=rt.digest_bits),
            rt.n_shards, rt.digest_bits,
        )
        want_degraded = sid == dead_shard
        assert want_degraded.any()  # the fixture must exercise the mask
        assert np.array_equal(res.degraded, want_degraded)
        # degraded keys read as misses with -1 sentinels ...
        assert not res.hit[want_degraded].any()
        assert (res.file_ids[want_degraded] == -1).all()
        assert (res.offsets[want_degraded] == -1).all()
        # ... while every healthy shard still answers
        assert res.hit[~want_degraded].all()
        assert rt.stats.degraded_keys == int(want_degraded.sum())
        assert rt.stats.degraded_batches == 1
        # legacy 3-tuple callers see plain misses, no exception
        fid, off, hit = rt.lookup_batch(probe_keys)
        assert np.array_equal(hit, res.hit)
    finally:
        rt.close()


def test_router_similarity_degrades_to_surviving_shards(store_dir, probe_keys):
    from repro.core.fingerprint import fingerprint_batch

    fps, _ = fingerprint_batch(probe_keys[:5], 256)
    st = IndexStore.open(store_dir)
    dead_shard = 3
    live = [
        s for s in range(st.n_shards)
        if s != dead_shard and int(st.manifest["shards"][s]["count"]) > 0
    ]
    want = merge_similar_topk(
        [st.similar_shard(s, fps, 4) for s in live], 4
    )
    rt, inj = _chaos_router(store_dir)
    try:
        for tr in inj:
            tr.kill(shard=dead_shard)
        res = rt.similar_batch_ex(fps, 4)
        assert res.degraded.all()  # a lost shard taints every query
        for got, ref in zip((res.scores, res.file_ids, res.offsets), want):
            assert np.array_equal(got, ref)
        assert rt.stats.degraded_similar == 1
    finally:
        rt.close()


def test_router_all_dead_fails_fast_within_backoff(store_dir, probe_keys):
    rt, inj = _chaos_router(store_dir, fail_threshold=1)
    try:
        for tr in inj:
            tr.kill()  # whole endpoint down, every shard
        r1 = rt.lookup_batch_ex(probe_keys[:40])
        assert r1.degraded.all()
        # inside the backoff window candidates() is empty: the next batch
        # degrades without probing (fail-fast taxonomy "dead")
        r2 = rt.lookup_batch_ex(probe_keys[:40])
        assert r2.degraded.all()
        kinds = set()
        for errs in rt.stats.errors_per_shard.values():
            kinds.update(errs)
        assert "dead" in kinds
    finally:
        rt.close()


def test_router_reraises_endpoint_bug_unscored(store_dir, probe_keys):
    """An exception that is not a TransportError (say, a kernel the device
    refused) reaches the caller on every call; it never marks the shard
    sick, so it can never turn into degraded results."""
    rt, inj = _chaos_router(store_dir, fail_threshold=1)

    def refused(*a, **kw):
        raise ValueError("kernel refused")

    try:
        for tr in inj:
            tr.inner.store.lookup_batch = refused
        for _ in range(3):
            with pytest.raises(ValueError, match="kernel refused"):
                rt.lookup_batch_ex(probe_keys[:40])
        assert not rt.health.has_unhealthy()
        assert rt.stats.probes_failed == 0
    finally:
        rt.close()


def test_chaos_acceptance_deterministic_degraded_and_recovery(
    store_dir, probe_keys
):
    """Acceptance: seeded chaos (1 dead shard + 1 slow shard) produces
    byte-identical degraded results across two runs, fires hedges, and
    returns byte-identical clean results after revival."""
    with ShardRouter(store_dir, replicas=2, min_scatter_keys=1) as clean:
        baseline = clean.lookup_batch(probe_keys)

    dead_shard, slow_shard = 2, 5

    def chaos_run():
        rt, inj = _chaos_router(
            store_dir, seed=42, probe_timeout_ms=400.0,
            hedge_floor_ms=5.0,
        )
        try:
            for tr in inj:
                tr.kill(shard=dead_shard)
                tr.set_latency(30.0, jitter_ms=10.0, shard=slow_shard)
            out = [rt.lookup_batch_ex(probe_keys) for _ in range(3)]
            stats = rt.stats
            # revive and wait out the probation backoff
            for tr in inj:
                tr.revive(shard=dead_shard)
                tr.clear()
            deadline = time.monotonic() + 10.0
            post = rt.lookup_batch_ex(probe_keys)
            while post.degraded.any() and time.monotonic() < deadline:
                time.sleep(0.1)
                post = rt.lookup_batch_ex(probe_keys)
            return out, post, stats, rt.health.snapshot()
        finally:
            rt.close()

    runs_a, post_a, stats_a, snap_a = chaos_run()
    runs_b, post_b, stats_b, snap_b = chaos_run()

    # the degraded results are deterministic: byte-identical across runs
    for ra, rb in zip(runs_a, runs_b):
        for col_a, col_b in zip(ra, rb):
            assert np.array_equal(col_a, col_b)
    # the slow shard pushed probes past the hedge point
    assert stats_a.hedges_fired > 0
    # degraded masks cover exactly the dead shard's key range
    sid = shard_of(
        digest_u64(probe_keys), 8, 64
    )
    assert np.array_equal(runs_a[0].degraded, sid == dead_shard)
    # post-revival: byte-identical to the no-fault baseline
    assert not post_a.degraded.any()
    for got, ref in zip(
        (post_a.file_ids, post_a.offsets, post_a.hit), baseline
    ):
        assert np.array_equal(got, ref)
    for got, ref in zip(
        (post_b.file_ids, post_b.offsets, post_b.hit), baseline
    ):
        assert np.array_equal(got, ref)
    assert snap_a["revivals"] >= 1
    assert snap_a["last_recovery_s"] > 0


# ---------------------------------------------------------------------------
# QueryService + loadgen under chaos
# ---------------------------------------------------------------------------

def test_service_threads_degraded_mask_through_batcher(corpus, store_dir):
    rstore, _ = corpus
    rt, inj = _chaos_router(store_dir)
    dead_shard = 4
    for tr in inj:
        tr.kill(shard=dead_shard)
    with QueryService(rstore, rt, ServiceConfig(replicas=2)) as svc:
        st = IndexStore.open(store_dir)
        keys = sorted(st.iter_keys())[:120]
        sid = shard_of(digest_u64(keys), st.n_shards, st.digest_bits)
        outs = {}

        def client(i):
            outs[i] = svc.lookup_batch(keys[i * 20:(i + 1) * 20])

        ths = [
            threading.Thread(target=client, args=(i,)) for i in range(6)
        ]
        for t in ths:
            t.start()
        for t in ths:
            t.join(10)
        for i, res in outs.items():
            want = (sid[i * 20:(i + 1) * 20] == dead_shard)
            assert np.array_equal(res.degraded, want)
            assert res.hit[~want].all()
        s = svc.stats()
        assert s["fault"]["degraded_keys"] == int(
            (sid[:120] == dead_shard).sum()
        )
        assert s["health"]["dead_domains"]
    rt.close()


def test_service_fetch_survives_dead_shard(corpus, store_dir):
    """fetch through a dead shard range: affected targets land in
    ``missing`` (the degraded contract), nothing raises, and every other
    record still round-trips byte-identically."""
    rstore, _ = corpus
    st = IndexStore.open(store_dir)
    keys = sorted(st.iter_keys())[:100]
    sid = shard_of(digest_u64(keys), st.n_shards, st.digest_bits)
    dead_shard = int(sid[0])  # guarantee at least one affected target
    rt, inj = _chaos_router(store_dir)
    for tr in inj:
        tr.kill(shard=dead_shard)
    with QueryService(rstore, rt, ServiceConfig(replicas=2)) as svc:
        res = svc.fetch(keys, verify=True)
        behind_dead = {k for k, s in zip(keys, sid) if s == dead_shard}
        assert behind_dead
        assert behind_dead <= set(res.missing)
        assert set(res.records) == set(keys) - set(res.missing)
        assert not res.mismatches
    rt.close()


def test_service_closed_loop_survives_a_shard_killed_mid_run(corpus, store_dir):
    """Closed-loop clients through the service while one shard range is
    killed and revived mid-run: no request fails (the outage shows only as
    degraded results), the degraded mask is exactly the dead shard's keys,
    and after revival the service answers as the store does."""
    rstore, _ = corpus
    rt, inj = _chaos_router(store_dir, fail_threshold=2)
    keys = sorted(IndexStore.open(store_dir).iter_keys())
    sid = shard_of(digest_u64(keys), rt.n_shards, rt.digest_bits)
    dead_shard = 3
    with QueryService(rstore, rt, ServiceConfig(replicas=2)) as svc:
        svc.lookup_batch(keys[:300])  # warm

        def chaos():
            time.sleep(0.2)
            for tr in inj:
                tr.kill(shard=dead_shard)
            time.sleep(0.4)
            for tr in inj:
                tr.revive(shard=dead_shard)

        chaos_thread = threading.Thread(target=chaos)
        chaos_thread.start()
        rep = run_closed_loop(
            svc.lookup_batch, keys, clients=6, duration_s=1.0,
            keys_per_request=8, classify=lambda r: bool(r.degraded.any()),
        )
        chaos_thread.join()
        assert rep.errors == 0, f"{rep.errors} client errors during chaos"
        assert rep.degraded > 0, "the kill window produced no degraded result"

        for tr in inj:
            tr.kill(shard=dead_shard)
        res = svc.lookup_batch(keys)
        assert np.array_equal(res.degraded, sid == dead_shard)
        assert res.hit[sid != dead_shard].all()
        for tr in inj:
            tr.revive(shard=dead_shard)

        ref = IndexStore.open(store_dir).lookup_batch(keys)
        deadline = time.monotonic() + 10.0
        res = svc.lookup_batch(keys)
        while res.degraded.any() and time.monotonic() < deadline:
            time.sleep(0.1)
            res = svc.lookup_batch(keys)
        assert not res.degraded.any(), "still degraded 10 s after revival"
        for got, want in zip((res.file_ids, res.offsets, res.hit), ref):
            assert np.array_equal(got, want)
        assert svc.stats()["health"]["revivals"] >= 1
    rt.close()


def test_loadgen_separates_failed_degraded_and_counters():
    calls = [0]

    class FakeResult:
        def __init__(self, degraded):
            self.degraded = np.array([degraded])

    def request_fn(keys):
        calls[0] += 1
        if calls[0] % 5 == 0:
            raise RuntimeError("injected request failure")
        return FakeResult(degraded=(calls[0] % 3 == 0))

    hedges = [0]

    def counters():
        hedges[0] += 1
        return {"hedges_fired": hedges[0] * 2}

    rep = run_closed_loop(
        request_fn, ["k1", "k2"], clients=2, duration_s=0.3,
        classify=lambda r: bool(r.degraded.any()),
        counters_fn=counters,
    )
    assert rep.errors > 0 and rep.failed == rep.errors
    assert rep.degraded > 0
    assert rep.requests > 0
    assert rep.counters["hedges_fired"] == 2  # delta of the two snapshots
    assert "failed" in rep.summary() and "hedges" in rep.summary()
