"""Zero-copy async read path: span backends, views, env knobs, fetch_async.

Complements test_extract_engine.py (engine parity/coalescing/cache) with
the backend-abstraction surface the async read path added: per-backend
byte parity on a collision-seeded corpus, the zero-copy RecordView
lifecycle (lazy decode, buffer release at the API boundary), fd hygiene
when a streaming consumer abandons early, the REPRO_READER_* env knobs,
verify-mode agreement, and the service's end-to-end async fetch.
"""

import gc
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import pytest

from repro.core import (
    RecordStore,
    build_index,
    extract,
    extract_iter,
    intersect_host,
    resolve_backend,
    uring_available,
)
from repro.core.extract import ExtractionResult, plan_extraction
from repro.core.iobackend import RecordView
from repro.core.reader import ReadStats, stream_plan
from repro.core.sdfgen import CorpusSpec, db_id_list, generate_corpus
from repro.core.verify import VerifyBatcher

KEY_BITS = 16  # collision-seeded: mismatch path is part of every parity run

BACKENDS = ["thread", "mmap"] + (["uring"] if uring_available() else [])


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
def hashed_index(corpus):
    store, _ = corpus
    return build_index(store, key_mode="hashed_key", key_bits=KEY_BITS)


@pytest.fixture(scope="module")
def serial_ref(corpus, targets, hashed_index):
    store, _ = corpus
    res = extract(store, hashed_index, targets, key_bits=KEY_BITS, workers=0)
    assert res.mismatches, "corpus no longer seeds collisions"
    return res


def _assert_identical(a: ExtractionResult, b: ExtractionResult):
    assert list(a.records.items()) == list(b.records.items())
    assert a.missing == b.missing
    assert a.mismatches == b.mismatches


# ---------------------------------------------------------------------------
# per-backend parity + stats surface
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_parity_collision_seeded(corpus, targets, hashed_index,
                                         serial_ref, backend):
    store, _ = corpus
    res = extract(store, hashed_index, targets, key_bits=KEY_BITS,
                  workers=3, backend=backend)
    _assert_identical(serial_ref, res)
    assert res.read_backend == backend
    assert res.inflight_peak >= 1
    assert res.verify_records >= res.found


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_parity_extract_iter(corpus, targets, hashed_index,
                                     serial_ref, backend):
    store, _ = corpus
    seen = dict(extract_iter(store, hashed_index, targets,
                             key_bits=KEY_BITS, workers=2, backend=backend))
    assert seen == serial_ref.records


def test_depth_caps_inflight_spans(corpus, targets, hashed_index):
    if "uring" not in BACKENDS:
        pytest.skip("no io_uring on this kernel")
    store, _ = corpus
    res = extract(store, hashed_index, targets, key_bits=KEY_BITS,
                  workers=1, backend="uring", depth=3)
    assert 1 <= res.inflight_peak <= 3


# ---------------------------------------------------------------------------
# env knobs (core/iobackend.py, core/reader.py)
# ---------------------------------------------------------------------------

def test_reader_backend_env_steers_auto(corpus, targets, hashed_index,
                                        serial_ref, monkeypatch):
    store, _ = corpus
    monkeypatch.setenv("REPRO_READER_BACKEND", "thread")
    res = extract(store, hashed_index, targets, key_bits=KEY_BITS, workers=2)
    assert res.read_backend == "thread"
    _assert_identical(serial_ref, res)


def test_reader_depth_env(corpus, targets, hashed_index, monkeypatch):
    if "uring" not in BACKENDS:
        pytest.skip("no io_uring on this kernel")
    store, _ = corpus
    monkeypatch.setenv("REPRO_READER_DEPTH", "2")
    res = extract(store, hashed_index, targets, key_bits=KEY_BITS,
                  workers=1, backend="uring")
    assert res.inflight_peak <= 2


def test_verify_backend_env_steers_auto(corpus, targets, hashed_index,
                                        serial_ref, monkeypatch):
    store, _ = corpus
    monkeypatch.setenv("REPRO_VERIFY_BACKEND", "string")
    res = extract(store, hashed_index, targets, key_bits=KEY_BITS, workers=2)
    _assert_identical(serial_ref, res)


def test_resolve_backend_names():
    be = resolve_backend(None)
    try:
        assert be.name == ("uring" if uring_available() else "thread")
    finally:
        be.close()
    for name in ("thread", "mmap"):
        be = resolve_backend(name)
        try:
            assert be.name == name
        finally:
            be.close()
    with pytest.raises(ValueError):
        resolve_backend("not-a-backend")


# ---------------------------------------------------------------------------
# zero-copy invariant
# ---------------------------------------------------------------------------

def test_record_views_are_zero_copy_until_decode(corpus, targets,
                                                 hashed_index):
    store, _ = corpus
    plan, _missing = plan_extraction(hashed_index, targets, KEY_BITS)
    stats = ReadStats()
    events = list(stream_plan(store, plan, verify=True, workers=1,
                              stats=stats, backend="thread"))
    assert events
    views = [ev.payload for ev in events if ev.ok]
    assert views and all(isinstance(v, RecordView) for v in views)
    for v in views:
        assert not v.decoded
        rr = v.raw_range()
        assert rr is not None  # still pinned to its span buffer
        raw, lo, hi = rr
        assert bytes(memoryview(raw)[lo:hi]).decode("utf-8") == v.text
        # decode boundary: the view no longer pins the buffer...
        assert v.decoded and v.raw_range() is None and v.mem() is None
        # ...but the memoized text survives
        assert v.text.endswith("$$$$\n") or "$$$$" not in v.text


def test_span_buffer_shared_within_coalesced_span(corpus, hashed_index):
    """Records coalesced into one span must carve views of ONE buffer."""
    store, _ = corpus
    # dense targets: consecutive records of one db => spans merge
    _, spec = corpus
    dense = db_id_list(spec, "chembl")[:40]
    plan, _ = plan_extraction(hashed_index, dense, KEY_BITS)
    events = [ev for ev in stream_plan(
        store, plan, verify=False, workers=1,
        coalesce_gap=1 << 20, stats=ReadStats(), backend="thread",
    ) if ev.ok and isinstance(ev.payload, RecordView)]
    bufs = {id(ev.payload._buf) for ev in events}
    assert len(bufs) < len(events), "no span sharing happened"


# ---------------------------------------------------------------------------
# abandoned consumers leak nothing
# ---------------------------------------------------------------------------

def _corpus_fds(root: Path) -> int:
    """Open fds (or mmaps via their /proc symlink targets) into ``root``.

    Counting *corpus* fds instead of the process total keeps the test
    immune to unrelated fd churn from background threads earlier test
    modules leave behind (executors, JAX runtime, fork pools).
    """
    n = 0
    prefix = str(root)
    for fd in os.listdir("/proc/self/fd"):
        try:
            if os.readlink(f"/proc/self/fd/{fd}").startswith(prefix):
                n += 1
        except OSError:
            continue
    return n


@pytest.mark.parametrize("backend", BACKENDS)
def test_abandoned_extract_iter_leaks_no_fds(corpus, targets, hashed_index,
                                             backend):
    store, _ = corpus
    for _ in range(3):
        it = extract_iter(store, hashed_index, targets, key_bits=KEY_BITS,
                          workers=2, backend=backend)
        for _ev, _ in zip(range(3), it):
            pass
        it.close()
    # close() drops queued files but deliberately does NOT join in-flight
    # file workers (abandon must not stall) — poll until they drain.  A
    # real leak never reaches zero.
    deadline = time.monotonic() + 10.0
    while _corpus_fds(store.root) and time.monotonic() < deadline:
        gc.collect()
        time.sleep(0.05)
    assert _corpus_fds(store.root) == 0


# ---------------------------------------------------------------------------
# verify modes agree
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["string", "vector", "process"])
def test_verify_modes_agree_with_reference(corpus, targets, hashed_index,
                                           serial_ref, mode):
    store, _ = corpus
    res = extract(store, hashed_index, targets, key_bits=KEY_BITS,
                  workers=2, verify_backend=mode)
    _assert_identical(serial_ref, res)


PROCESS_VERIFY_AFTER_JAX = """
import sys, tempfile
from pathlib import Path
import jax.numpy as jnp
from repro.core import RecordStore, build_index, extract
from repro.core.sdfgen import CorpusSpec, db_id_list, generate_corpus
jnp.zeros(8).block_until_ready()  # JAX and its threads are live
root = Path(tempfile.mkdtemp()) / "corpus"
spec = CorpusSpec(n_files=2, records_per_file=200, key_bits=16)
generate_corpus(root, spec)
store = RecordStore(root)
idx = build_index(store, key_mode="hashed_key", key_bits=16)
targets = db_id_list(spec, "chembl")
want = extract(store, idx, targets, key_bits=16, workers=0)
got = extract(store, idx, targets, key_bits=16, workers=2,
              verify_backend="process")
assert got.records == want.records and got.mismatches == want.mismatches
assert len(got.records) > 20
print("OK")
"""


def test_process_verify_never_forks_a_live_jax_process(tmp_path):
    """The process verify pool starts its workers as fresh interpreters, so
    a parent that has JAX running is never forked (JAX warns on such a
    fork: its threads do not survive it)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    proc = subprocess.run(
        [sys.executable, "-W", "default", "-c", PROCESS_VERIFY_AFTER_JAX],
        capture_output=True, text=True, env=env, timeout=300, cwd=tmp_path,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip().endswith("OK")
    assert "fork()" not in proc.stderr, proc.stderr[-3000:]


def test_verify_batcher_counts_batches():
    vb = VerifyBatcher("vector")
    stats = ReadStats()
    recs = [
        "junk\n  repro    junk\n    0.0000    0.0000    0.0000 C   0\n",
    ]
    ok, ids = vb.verify(["InChI=1S/nope"], recs, None, stats)
    assert ok == [False] and len(ids) == 1
    assert stats.verify_records == 1 and stats.verify_batches >= 1


# ---------------------------------------------------------------------------
# service: async end-to-end fetch
# ---------------------------------------------------------------------------

def test_fetch_async_parity_and_read_stats(corpus, targets, hashed_index):
    from repro.service import QueryService, ServiceConfig

    store, _ = corpus
    sdir = Path(tempfile.mkdtemp()) / "istore"
    hashed_index.save_sharded(sdir, n_shards=4)
    with QueryService(store, sdir, ServiceConfig(replicas=1)) as svc:
        sync = svc.fetch(targets, key_bits=KEY_BITS)
        fut = svc.fetch_async(targets, key_bits=KEY_BITS)
        res = fut.result(timeout=60)
        _assert_identical(sync, res)
        s = svc.stats()["read"]
        for key in ("backend", "spans_read", "bytes_read", "records",
                    "inflight_peak", "verify_batches", "verify_records",
                    "verify_batch_max"):
            assert key in s, key
        assert s["backend"] in ("uring", "thread", "mmap", "serial")
        assert s["records"] > 0 and s["verify_records"] > 0
