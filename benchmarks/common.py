"""Shared benchmark infrastructure: cached corpus + timing helpers.

The benchmark corpus is a scale model of the paper's (354 files × 500k
records, 3.2 TB): N_FILES × RECORDS_PER_FILE synthetic SDF records
(~tens of MB).  Every benchmark reports its measured value AND, where the
paper's complexity model applies, the projection to paper scale —
reproducing how the paper itself extrapolated (Eq. 2/3 project the
100-day baseline from 3 scanned files).

Output convention (benchmarks/run.py): ``name,us_per_call,derived`` CSV.
"""

from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Callable, Optional, Tuple

from repro.core.records import RecordStore
from repro.core.sdfgen import CorpusSpec, generate_corpus

# paper-scale constants (§III)
PAPER_N_FILES = 354
PAPER_RECORDS_PER_FILE = 500_000
PAPER_N_RECORDS = 176_929_690
PAPER_N_TARGETS = 477_123
PAPER_FOUND = 435_413
PAPER_FINAL = 426_850

# REPRO_BENCH_SCALE multiplies records-per-file (``run.py --scale``): the
# stock corpus fits in one coalesce window per file, so backend and depth
# effects only separate once the corpus is 10-100x deeper.
BENCH_SCALE = int(os.environ.get("REPRO_BENCH_SCALE", "1"))
BENCH_FILES = int(os.environ.get("REPRO_BENCH_FILES", "8"))
BENCH_RPF = int(os.environ.get("REPRO_BENCH_RPF", "4000")) * BENCH_SCALE
CACHE = Path(
    os.environ.get(
        "REPRO_BENCH_CACHE", Path(__file__).resolve().parents[1] / ".bench_cache"
    )
)


def bench_spec(key_bits: int = 64) -> CorpusSpec:
    return CorpusSpec(
        n_files=BENCH_FILES, records_per_file=BENCH_RPF, key_bits=key_bits
    )


def bench_store(key_bits: int = 64) -> Tuple[RecordStore, CorpusSpec]:
    spec = bench_spec(key_bits)
    root = CACHE / f"corpus_{spec.n_files}x{spec.records_per_file}_{key_bits}"
    generate_corpus(root, spec)
    return RecordStore(root), spec


def timeit(fn: Callable, repeats: int = 1) -> Tuple[float, object]:
    """(seconds_per_call, last_result)."""
    t0 = time.perf_counter()
    out = None
    for _ in range(repeats):
        out = fn()
    return (time.perf_counter() - t0) / repeats, out


def row(name: str, seconds: float, derived: str = "") -> str:
    return f"{name},{seconds * 1e6:.1f},{derived}"
