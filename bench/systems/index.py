"""The index tier as deployed: publish a key set, serve it through QueryService.

Set-up makes the key set from the seed, publishes it with the program's
own ``save_sharded`` into a temporary directory, stands up
``QueryService`` with its default ``ServiceConfig`` (on a TPU that is the
device digest probe), and warms every device program shape the cell's
traffic can reach.  The window is a closed loop of the cell's clients;
afterwards the traffic kind compares the answers with the configuration's
plain reference.

Of its traffic kind (``bench/traffic/kinds/<kind>.py``) this driver asks,
besides the streams and the call, ``warm(ctx, state, store, probe)``,
``check(ctx, state, sent, reference)`` and ``control(ctx, state,
reference)``.
"""

from __future__ import annotations

import shutil
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from bench import keygen, loadgen, workload

__all__ = ["setup", "trace_mark", "measure", "end_to_end", "attempted_failed",
           "requests_in_window", "check", "control", "sizes_of"]

# candidate keys per stored key: the surplus fills every shard to the same
# size and supplies the absent keys
_OVERSHOOT = 1.10


@dataclass
class State:
    conf: dict
    kind: Any                       # the traffic kind's module
    work: Path
    svc: Any
    keys: List[str]                 # stored keys, then absent keys
    n_present: int
    ref_fid: np.ndarray             # reference location of each stored key
    ref_off: np.ndarray
    streams: List[np.ndarray]
    counters: Dict[str, Dict[str, float]] = field(default_factory=dict)
    window: Optional[loadgen.Window] = None
    timings: Dict[str, float] = field(default_factory=dict)
    checked: list = field(default_factory=list)   # the answers compared


def sizes_of(conf: dict) -> Dict[str, int]:
    return {"n_present": conf["keys"], "n_absent": conf["absent_keys"]}


def _make_keys(conf: dict, seed: int):
    """Stored keys, equal in number in every digest-range shard, plus the
    absent keys, all from ``seed``."""
    n, shards = conf["keys"], conf["n_shards"]
    extra = conf["absent_keys"]
    per = n // shards
    while True:
        cand = keygen.make_keys(np.arange(int(n * _OVERSHOOT) + extra), seed)
        top = (keygen.digests(cand) >> np.uint64(64 - (shards - 1).bit_length())
               ).astype(np.int64)
        groups = [np.nonzero(top == s)[0] for s in range(shards)]
        if min(len(g) for g in groups) >= per:
            break
        n = int(n * 1.05)  # a shard came up short: draw more candidates
    stored = np.sort(np.concatenate([g[:per] for g in groups]))
    rest = np.setdiff1d(np.arange(len(cand)), stored)
    if len(rest) < extra:
        raise ValueError("too few surplus keys for the absent pool")
    return [cand[i] for i in stored], [cand[i] for i in rest[:extra]]


def setup(ctx) -> State:
    from repro.core.index import ByteOffsetIndex
    from repro.core.records import RecordStore
    from repro.core.store import IndexStore
    from repro.service import QueryService, ServiceConfig

    conf = dict(ctx.config)
    timings: Dict[str, float] = {}
    t = time.perf_counter()
    stored, absent = _make_keys(conf, ctx.seed)
    timings["keys_s"] = time.perf_counter() - t

    # synthetic record locations: PubChem's files of 500,000 records, each
    # record a seeded size around 2 KB (no record file exists: fetch is not
    # in these cells)
    n = len(stored)
    rng = np.random.default_rng([ctx.seed, 0x10C])
    per_file = conf["records_per_file"]
    ref_fid = (np.arange(n) // per_file).astype(np.int32)
    sizes = rng.integers(conf["record_bytes"][0], conf["record_bytes"][1], n)
    ref_off = np.cumsum(sizes) - sizes
    ref_off -= ref_off[ref_fid * per_file]          # offsets restart per file
    names = [f"Compound_{i:06d}.sdf" for i in range(int(ref_fid[-1]) + 1)]
    idx = ByteOffsetIndex(key_mode="full_id")
    idx.entries = {k: (names[f], int(o))
                   for k, f, o in zip(stored, ref_fid.tolist(), ref_off.tolist())}

    work = Path(tempfile.mkdtemp(prefix="bench_index_"))
    try:
        t = time.perf_counter()
        idx.save_sharded(
            work / "store", n_shards=conf["n_shards"],
            digest_bits=conf["digest_bits"],
            bloom_bits_per_key=conf["bloom_bits_per_key"],
            fingerprint_bits=conf["fingerprint_bits"],
        )
        timings["publish_s"] = time.perf_counter() - t
        del idx
        (work / "records").mkdir()
        svc = QueryService(RecordStore(work / "records"), work / "store",
                           ServiceConfig(**conf["service"]))
        kind = ctx.kind
        state = State(
            conf=conf, kind=kind, work=work, svc=svc, keys=stored + absent,
            n_present=n, ref_fid=ref_fid, ref_off=ref_off,
            streams=workload.streams(kind, ctx.traffic, sizes_of(conf), ctx.seed),
            timings=timings,
        )

        # warm every device shape the traffic reaches, then the traffic
        # itself, on a seed of its own, through the service
        t = time.perf_counter()
        kind.warm(ctx, state, IndexStore.open(work / "store"),
                  conf["service"].get("probe") or "auto")
        timings["warm_shapes_s"] = time.perf_counter() - t
        t = time.perf_counter()
        warm = workload.streams(kind, ctx.traffic, sizes_of(conf),
                                ctx.seed ^ 0x5EED)
        loadgen.closed_loop(kind.call(state), warm, conf["warm_seconds"],
                            span="bench.warm")
        timings["warm_traffic_s"] = time.perf_counter() - t
        return state
    except BaseException:
        shutil.rmtree(work, ignore_errors=True)
        raise


def _counters(svc) -> Dict[str, float]:
    st = svc.stats()
    return {
        "batches": st["scheduler"]["batches"],
        "batch_keys": st["scheduler"]["keys"],
        "hedges_fired": st["fault"]["hedges_fired"],
        "shard_probes": st["router"]["shard_probes"],
        "degraded_keys": st["fault"]["degraded_keys"],
    }


def trace_mark(ctx, state: State, which: str) -> None:
    """Nothing to snapshot: the index readers use whole-window counters."""


def measure(ctx, state: State, on_start=None) -> loadgen.Window:
    state.counters["before"] = _counters(state.svc)
    state.window = loadgen.closed_loop(
        state.kind.call(state), state.streams, ctx.seconds,
        span=state.kind.span, on_start=on_start,
    )
    state.counters["after"] = _counters(state.svc)
    return state.window


def end_to_end(ctx, state: State) -> Dict[str, float]:
    """``requests_per_s``: requests completed inside the window over its
    length; ``request_p95_ms``: 95th percentile of every request sent in
    it, a failed one counting as slower than any."""
    w = state.window
    sent = [r for r in w.records if r.t_send < w.t_end]
    done = [r for r in sent if r.error is None and r.t_done <= w.t_end]
    lat = np.array([np.inf if r.error is not None else (r.t_done - r.t_send) * 1e3
                    for r in sent])
    return {
        "requests_per_s": len(done) / w.seconds,
        "request_p95_ms": float(np.percentile(lat, 95, method="higher")),
    }


def attempted_failed(state: State):
    w = state.window
    sent = [r for r in w.records if r.t_send < w.t_end]
    return len(sent), sum(r.error is not None for r in sent)


def requests_in_window(state: State) -> int:
    return attempted_failed(state)[0]


def check(ctx, state: State, reference) -> List[tuple]:
    """``(name, value, limit)`` of each number compared; frees the service."""
    w = state.window
    sent = [r for r in w.records if r.t_send < w.t_end and r.error is None]
    state.svc.close()
    try:
        return state.kind.check(ctx, state, sent, reference)
    finally:
        shutil.rmtree(state.work, ignore_errors=True)


def control(ctx, state: State, reference) -> List[tuple]:
    """The same numbers with the control answering in the program's place
    (after :func:`check`, on the answers it compared)."""
    return state.kind.control(ctx, state, reference)
