"""Similarity-search benchmark — batched Tanimoto top-k vs per-query loop.

Three scorers over the same fingerprint plane (library level):

* ``similarity.naive_loop`` — :func:`tanimoto_topk_naive`, the
  pre-batching serving contract: one independent scoring pass per query,
  database popcounts recomputed on every call;
* ``similarity.reference``  — the chunked vectorized NumPy oracle
  (:func:`tanimoto_topk_ref`) with precomputed count sidecars;
* ``similarity.kernel``     — the :func:`tanimoto_topk` dispatcher's
  resolved backend: the Pallas popcount/top-k kernel on TPU, the
  L2-tiled uint64 host path elsewhere.

All three must produce byte-identical ``(scores, indices)`` — the
``parity`` flags gate the throughput numbers, and an interpret-mode
Pallas pass on a subsample keeps the kernel itself honest on CPU-only
boxes.  Then the full service path is driven by closed-loop clients:
per-query host probes (one fingerprint per ``similar_batch`` call)
against ``QueryService.similar`` riding the micro-batching scheduler.
``benchmarks/run.py`` writes :func:`last_metrics` to
``BENCH_similarity.json``.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

import numpy as np

from repro.core import build_index
from repro.core.fingerprint import fingerprint_batch, popcount_u32
from repro.core.store import IndexStore
from repro.kernels.tanimoto.ops import tanimoto_topk
from repro.kernels.tanimoto.ref import tanimoto_topk_naive, tanimoto_topk_ref
from repro.service import QueryService, ServiceConfig, run_closed_loop

from .common import CACHE, bench_store, row, timeit

CLIENTS = int(os.environ.get("REPRO_BENCH_SIM_CLIENTS", "8"))
QUERIES_PER_REQUEST = 4
DURATION_S = float(os.environ.get("REPRO_BENCH_SIM_SECONDS", "1.2"))
N_QUERIES = int(os.environ.get("REPRO_BENCH_SIM_QUERIES", "64"))
N_SHARDS = 16
REPLICAS = 2
K = 8

_LAST: Optional[Dict[str, object]] = None


def last_metrics() -> Optional[Dict[str, object]]:
    """Metrics of the most recent :func:`run` (for BENCH_similarity.json)."""
    return _LAST


def _report(rep) -> Dict[str, float]:
    return {
        "clients": rep.clients,
        "requests": rep.requests,
        "queries_per_sec": rep.lookups_per_sec,
        "p50_ms": rep.p50_ms,
        "p99_ms": rep.p99_ms,
        "errors": rep.errors,
    }


def _equal(a, b) -> bool:
    return np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def run() -> List[str]:
    global _LAST
    store, spec = bench_store()
    out = []

    idx = build_index(store, key_mode="full_id")
    store_dir = CACHE / (
        f"store_{spec.n_files}x{spec.records_per_file}_{N_SHARDS}"
    )
    idx.save_sharded(store_dir, n_shards=N_SHARDS)
    keys = sorted(idx.entries.keys())

    # the same folding the published sidecars carry — one flat plane for
    # the library-level rows, the sharded store for the service rows
    db, dc = fingerprint_batch(keys)
    step = max(1, len(keys) // N_QUERIES)
    qf = np.ascontiguousarray(db[::step][:N_QUERIES])
    qc = popcount_u32(qf).sum(axis=1, dtype=np.int32)
    qn = qf.shape[0]

    from repro.device import on_tpu

    backend = "pallas-tpu" if on_tpu() else "host-blocked"

    # warm every path (allocators, and the jit cache when a TPU is there)
    tanimoto_topk_naive(qf[:2], db, K)
    tanimoto_topk_ref(qf[:8], db, K, db_counts=dc)
    tanimoto_topk(qf[:8], db, K, db_counts=dc)

    t_naive, res_naive = timeit(lambda: tanimoto_topk_naive(qf, db, K))
    t_ref, res_ref = timeit(
        lambda: tanimoto_topk_ref(qf, db, K, q_counts=qc, db_counts=dc)
    )
    t_kern, res_kern = timeit(
        lambda: tanimoto_topk(qf, db, K, q_counts=qc, db_counts=dc)
    )
    qps_naive = qn / t_naive
    qps_ref = qn / t_ref
    qps_kern = qn / t_kern
    speedup_kern = qps_kern / max(qps_naive, 1e-9)
    speedup_ref = qps_ref / max(qps_naive, 1e-9)

    parity_kernel = _equal(res_kern, res_ref) and _equal(res_naive, res_ref)
    # the Pallas kernel itself, interpreted on a subsample (full-scale
    # interpret mode would dominate the bench on CPU-only boxes)
    sub_q, sub_n = min(qn, 16), min(len(keys), 512)
    parity_interpret = _equal(
        tanimoto_topk(qf[:sub_q], db[:sub_n], K, interpret=True),
        tanimoto_topk_ref(qf[:sub_q], db[:sub_n], K),
    )

    out.append(row(
        "similarity.naive_loop", t_naive,
        f"{qps_naive:.0f} q/s — {qn} queries x {len(keys)} rows, "
        f"one scoring pass per query"))
    out.append(row(
        "similarity.reference", t_ref,
        f"{qps_ref:.0f} q/s ({speedup_ref:.1f}x naive), chunked oracle"))
    out.append(row(
        "similarity.kernel", t_kern,
        f"{qps_kern:.0f} q/s ({speedup_kern:.1f}x naive) via {backend}, "
        f"top-{K} byte-identical={'ok' if parity_kernel else 'BROKEN'}, "
        f"interpret={'ok' if parity_interpret else 'BROKEN'}"))

    # -- service path: per-query probes vs the micro-batched scheduler -----
    svc = QueryService(
        store, store_dir, ServiceConfig(replicas=REPLICAS, max_batch=512)
    )
    naive_store = IndexStore.open(store_dir)

    sample = qf[: min(qn, 16)]
    got = svc.similar(sample, K)
    want_parts = [
        naive_store.similar_batch(sample[i : i + 1], K, probe="host")
        for i in range(sample.shape[0])
    ]
    want = tuple(
        np.concatenate([p[j] for p in want_parts], axis=0) for j in range(3)
    )
    parity_service = all(np.array_equal(got[j], want[j]) for j in range(3))
    out.append(row(
        "similarity.service_parity", 0.0,
        f"service vs per-query probes byte-identical="
        f"{'ok' if parity_service else 'BROKEN'}"))

    pool_step = max(1, len(keys) // 2048)
    pool = [db[i] for i in range(0, len(keys), pool_step)]

    def naive_sim(rows_):
        for fp in rows_:
            naive_store.similar_batch(fp[None, :], K, probe="host")

    rep_naive = run_closed_loop(
        naive_sim, pool, clients=CLIENTS, duration_s=DURATION_S,
        keys_per_request=QUERIES_PER_REQUEST,
    )
    out.append(row(
        "similarity.service_naive", rep_naive.seconds,
        f"{rep_naive.lookups_per_sec:.0f} q/s, {CLIENTS} clients x "
        f"{QUERIES_PER_REQUEST} queries/req, p50 {rep_naive.p50_ms:.2f} ms, "
        f"p99 {rep_naive.p99_ms:.2f} ms"))

    rep_svc = run_closed_loop(
        lambda rows_: svc.similar(np.stack(rows_), K), pool,
        clients=CLIENTS, duration_s=DURATION_S,
        keys_per_request=QUERIES_PER_REQUEST,
    )
    speedup_svc = rep_svc.lookups_per_sec / max(
        rep_naive.lookups_per_sec, 1e-9
    )
    sim_stats = svc.stats()["similarity"]
    sch = sim_stats["scheduler"]
    out.append(row(
        "similarity.service_batched", rep_svc.seconds,
        f"{rep_svc.lookups_per_sec:.0f} q/s ({speedup_svc:.1f}x naive), "
        f"mean batch {sch['mean_batch_keys']:.1f} queries, "
        f"p50 {rep_svc.p50_ms:.2f} ms, p99 {rep_svc.p99_ms:.2f} ms"))

    parity = bool(parity_kernel and parity_interpret and parity_service)
    _LAST = {
        "corpus": {
            "files": spec.n_files,
            "records_per_file": spec.records_per_file,
            "entries": len(keys),
            "n_shards": N_SHARDS,
            "fingerprint_bits": int(naive_store.fingerprint_bits or 0),
        },
        "config": {
            "n_queries": qn,
            "k": K,
            "backend": backend,
            "clients": CLIENTS,
            "queries_per_request": QUERIES_PER_REQUEST,
            "duration_s": DURATION_S,
            "replicas": REPLICAS,
        },
        "qps": {
            "naive_loop": qps_naive,
            "reference": qps_ref,
            "kernel": qps_kern,
        },
        "speedup_kernel_vs_naive": speedup_kern,
        "speedup_reference_vs_naive": speedup_ref,
        "service": {
            "naive": _report(rep_naive),
            "service": _report(rep_svc),
            "speedup_vs_naive": speedup_svc,
            "mean_coalesced_batch": sch["mean_batch_keys"],
            "fp_rows_scanned": sim_stats["fp_rows_scanned"],
        },
        "parity_flags": {
            "kernel_vs_reference": bool(parity_kernel),
            "interpret_kernel_vs_reference": bool(parity_interpret),
            "service_vs_per_query": bool(parity_service),
        },
        "parity": parity,
    }
    svc.close()
    return out
