"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (harness convention) covering:

  Table I   — baseline scan throughput + Eq. 2/3 projection
  Table II  — baseline vs indexed speedup (740× headline)
  Table III — storage / RAM / disk-I/O-volume trade-offs
  Table IV  — hashed-key vs full-id identifier strategies
  Eq. 4/5   — collision counts vs birthday bound + §VI discovery/migration
  Fig. 2    — runtime scaling and baseline/index crossover
  extract   — serial vs pipelined extraction engine (+ record cache)
  service   — continuous-batching query service vs per-key probing
  serve     — decode-token continuous batching vs static LM batches
  kernels   — TPU-adapted hot-loop throughput (hash_mix, sorted_probe)

Corpus scale via REPRO_BENCH_FILES / REPRO_BENCH_RPF env vars, or
``--scale N`` (→ REPRO_BENCH_SCALE) to multiply records-per-file 10-100x
so span-backend and depth effects separate from fixed overheads.
Roofline numbers come from the dry-run (results/dryrun.jsonl), not here.

The extraction-engine, service, similarity, and LM-serving modules
additionally emit machine-readable metrics (``BENCH_extract.json`` /
``BENCH_service.json`` / ``BENCH_similarity.json`` /
``BENCH_serve.json``) so records/sec, cache hit rate, sustained
lookups/sec, tokens/sec, p50/p99 latency, and the batching speedups are
tracked across PRs.  The committed copies at the repo root are only
rewritten with ``--update-metrics`` (run it on a quiet box when
regenerating the tracked numbers); plain runs park their metrics in the
bench cache so a smoke pass never churns the committed files.
``REPRO_BENCH_EXTRACT_OUT`` / ``REPRO_BENCH_SERVICE_OUT`` /
``REPRO_BENCH_SIMILARITY_OUT`` / ``REPRO_BENCH_SERVE_OUT`` override the
destination outright.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

from repro.device import use_compile_cache


def _write_metrics(
    metrics, env_var: str, default_name: str, tag: str, update: bool
) -> None:
    if not metrics:
        return
    out = os.environ.get(env_var)
    if out:
        path = Path(out)
    elif update:
        path = Path(__file__).resolve().parents[1] / default_name
    else:
        from .common import CACHE

        CACHE.mkdir(parents=True, exist_ok=True)
        path = CACHE / default_name
    path.write_text(json.dumps(metrics, indent=1, sort_keys=True) + "\n")
    print(f"{tag}.metrics_written,0,{path}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--scale", type=int, default=None, metavar="N",
        help="multiply records-per-file by N (10-100x separates backend "
             "and depth effects; exported as REPRO_BENCH_SCALE)")
    ap.add_argument(
        "--update-metrics", action="store_true",
        help="rewrite the committed BENCH_*.json files at the repo root; "
             "without it metrics land in the bench cache (env overrides "
             "such as REPRO_BENCH_EXTRACT_OUT always win)")
    args = ap.parse_args()
    use_compile_cache()
    if args.scale is not None:
        # must land in the env before the bench modules import common.py
        os.environ["REPRO_BENCH_SCALE"] = str(args.scale)
    from . import (
        collisions_eq45,
        extract_engine,
        fig2_scaling,
        kernels_tpu,
        serve_tokens,
        service_load,
        similarity,
        table1_scan,
        table2_speedup,
        table3_resources,
        table4_identifiers,
    )

    modules = [
        ("table1", table1_scan),
        ("table2", table2_speedup),
        ("table3", table3_resources),
        ("table4", table4_identifiers),
        ("eq45", collisions_eq45),
        ("fig2", fig2_scaling),
        ("extract", extract_engine),
        ("service", service_load),
        ("serve", serve_tokens),
        ("similarity", similarity),
        ("kernels", kernels_tpu),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        t0 = time.perf_counter()
        try:
            for line in mod.run():
                print(line, flush=True)
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{name}.ERROR,0,{type(e).__name__}: {e}", flush=True)
        print(
            f"{name}.total,{(time.perf_counter()-t0)*1e6:.0f},",
            flush=True,
        )
    _write_metrics(extract_engine.last_metrics(),
                   "REPRO_BENCH_EXTRACT_OUT", "BENCH_extract.json",
                   "extract", args.update_metrics)
    _write_metrics(service_load.last_metrics(),
                   "REPRO_BENCH_SERVICE_OUT", "BENCH_service.json",
                   "service", args.update_metrics)
    _write_metrics(similarity.last_metrics(),
                   "REPRO_BENCH_SIMILARITY_OUT", "BENCH_similarity.json",
                   "similarity", args.update_metrics)
    _write_metrics(serve_tokens.last_metrics(),
                   "REPRO_BENCH_SERVE_OUT", "BENCH_serve.json",
                   "serve", args.update_metrics)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
