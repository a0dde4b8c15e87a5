#!/usr/bin/env python3
"""Record a short trace of the program's own spans beside the device's work.

    python tests/bench/capture_program_trace.py <out.xplane.pb>

A small published store (4,096 keys in 16 shards, 2 replicas) served by
``QueryService`` with the device digest probe, and a ``ContinuousEngine``
over a two-layer model at test widths.  Every shape is warmed first.
Then, inside the benchmark's window markers, 8 client threads send 4
lookups of 4 keys each (coalesced batches of 8 or more keys scatter over
the router's gather threads), and the engine serves 3 prompts for a few
decode steps.  On a TPU this writes ``data/v5e_program.xplane.pb``, which
``test_program_spans.py`` reads; the same tests run :func:`record` on the
CPU, where the probe runs its jnp reference.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
import threading
from pathlib import Path
from typing import Dict

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

KEYS, SHARDS, CLIENTS, CALLS, KEYS_PER_CALL = 4096, 16, 8, 4, 4
PROMPTS, MAX_NEW = ["InChI=1S/C4H10", "InChI=1S/CH4O/c1-2", "x" * 20], 5


def _keys():
    """``KEYS`` keys, the same number in every shard (one table shape),
    and the shard of each."""
    import numpy as np

    from bench import keygen

    cand = keygen.make_keys(np.arange(2 * KEYS), 7)
    sid = (keygen.digests(cand) >> np.uint64(64 - (SHARDS - 1).bit_length())
           ).astype(np.int64)
    take = np.sort(np.concatenate(
        [np.nonzero(sid == s)[0][: KEYS // SHARDS] for s in range(SHARDS)]))
    return [cand[i] for i in take], sid[take]


def _service(work: Path, keys):
    from repro.core.index import ByteOffsetIndex
    from repro.core.records import RecordStore
    from repro.service import QueryService, ServiceConfig

    idx = ByteOffsetIndex(key_mode="full_id")
    idx.entries = {k: ("f.sdf", 100 * i) for i, k in enumerate(keys)}
    idx.save_sharded(work / "store", n_shards=SHARDS, fingerprint_bits=None)
    (work / "records").mkdir()
    svc = QueryService(RecordStore(work / "records"), work / "store",
                       ServiceConfig(probe="device", min_scatter_keys=8))
    return svc


def _engine():
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.models.registry import build_model
    from repro.serve.engine import ServeConfig
    from repro.serve.kvcache import PagedCacheSpec
    from repro.serve.scheduler import ContinuousEngine

    cfg = dataclasses.replace(
        get_config("yi-6b"), n_layers=2, d_model=64, n_heads=2, n_kv_heads=2,
        head_dim=32, d_ff=128, vocab_size=300)
    params, _ = build_model(cfg).init(jax.random.PRNGKey(0))
    spec = PagedCacheSpec(n_blocks=33, block_size=8, max_slots=4,
                          max_blocks_per_seq=8)
    return ContinuousEngine(cfg, params, spec,
                            ServeConfig(max_new_tokens=MAX_NEW, max_len=64))


def _burst(svc, keys) -> None:
    def client(c: int) -> None:
        for i in range(CALLS):
            lo = (c * CALLS + i) * KEYS_PER_CALL * 37 % (KEYS - KEYS_PER_CALL)
            svc.lookup_batch(keys[lo: lo + KEYS_PER_CALL])

    threads = [threading.Thread(target=client, args=(c,)) for c in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def record(out_dir: Path) -> Dict[str, object]:
    """Warm, then trace the burst and the engine into ``out_dir``; returns
    the trace file and the counters read before and after the window."""
    import jax

    from repro.core.store import IndexStore

    work = Path(tempfile.mkdtemp(prefix="program_trace_"))
    try:
        keys, sid = _keys()
        svc = _service(work, keys)
        engine = _engine()
        # every probe shape: 1 .. all the keys in flight reaching one shard
        shard0 = [k for k, s in zip(keys, sid) if s == 0]
        store = IndexStore.open(work / "store")
        for q in range(1, CLIENTS * KEYS_PER_CALL + 1):
            store.lookup_batch(shard0[:q], probe="device")
        _burst(svc, keys)
        for _ in range(2):  # full prefill, then the prefix-cache suffix path
            engine.generate(PROMPTS, max_new_tokens=MAX_NEW)
        before = {"service": svc.stats(), "engine": engine.counters()}

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(out_dir), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window_start"):
                pass
            with jax.profiler.TraceAnnotation("bench.lookup"):
                _burst(svc, keys)
            with jax.profiler.TraceAnnotation("bench.chat"):
                engine.generate(PROMPTS, max_new_tokens=MAX_NEW)
            with jax.profiler.TraceAnnotation("bench.window_stop"):
                pass
        finally:
            jax.profiler.stop_trace()
        after = {"service": svc.stats(), "engine": engine.counters()}
        svc.close()
        engine.close()
    finally:
        shutil.rmtree(work, ignore_errors=True)
    found = sorted(Path(out_dir).rglob("*.xplane.pb"))
    return {"file": found[-1], "before": before, "after": after}


def main(out: str) -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("capture_program_trace: no TPU", file=sys.stderr)
        return 2
    tmp = Path(tempfile.mkdtemp(prefix="program_trace_out_"))
    try:
        got = record(tmp)
        dest = Path(out)
        dest.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(got["file"], dest)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {dest} ({dest.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
