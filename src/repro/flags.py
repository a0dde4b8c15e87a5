"""Global trace-time flags.

``unroll_scans`` — when set, layer-stack scans and the chunked-xent loop
are fully unrolled at trace time.  Used ONLY by the dry-run's roofline
probe compiles: XLA's ``cost_analysis`` counts a while-loop body once
regardless of trip count, so scanned stacks under-report FLOPs/bytes by a
factor of n_layers.  The probes compile 1-block and 2-block unrolled
variants and extrapolate exactly (stacks are uniform by construction).
Production code paths keep scans rolled (small HLO, fast compiles).
"""

from __future__ import annotations

import contextlib
import os

_UNROLL = False

# Attention implementation for the XLA (non-Pallas) path:
#   "chunked" — online-softmax scan over KV chunks (default; beyond-paper
#               §Perf optimization — no S×S score materialization)
#   "ref"     — unblocked reference (the paper-faithful framework baseline;
#               used for oracle tests and §Perf before/after runs)
ATTN_IMPL = os.environ.get("REPRO_ATTN_IMPL", "chunked")
ATTN_CHUNK = int(os.environ.get("REPRO_ATTN_CHUNK", "1024"))

# Sequence-parallel layer outputs (Megatron SP): constrain attention/MLP
# outputs to the sequence-sharded residual layout so GSPMD lowers the TP
# combine as reduce-scatter (half the wire bytes of all-reduce) and the
# norm/residual region computes seq-sharded.  §Perf optimization; set
# REPRO_SP_OUTPUTS=0 for the baseline layout.
SP_OUTPUTS = os.environ.get("REPRO_SP_OUTPUTS", "1") == "1"

# Chunked decode attention — off by default: under (batch, seq→model) cache
# sharding the chunk reshape reshards the cache (measured: collective term
# 0 → 3.4 s on qwen2 decode_32k).  See EXPERIMENTS.md §Perf.
DECODE_CHUNKED = os.environ.get("REPRO_DECODE_CHUNKED", "0") == "1"

# Remat policy for the layer scan:
#   "names"   — save attn/ffn outputs (post-TP-collective tensors): backward
#               does not re-run the forward all-reduces (≈⅓ of TP collective
#               bytes) nor the forward matmuls (8ND→6ND FLOPs), costing two
#               seq-sharded (B,S/model,D) saves per layer.  §Perf default.
#   "nothing" — full remat (the framework baseline).
REMAT_POLICY = os.environ.get("REPRO_REMAT_POLICY", "names")


# Read-engine knobs (read dynamically, not at import: tests and launchers
# flip them per run):
#   REPRO_READER_BACKEND — span I/O backend for core.reader / the service:
#       "auto"   — io_uring when the kernel supports it, else "thread"
#       "uring"  — raw io_uring submission queue (Linux; depth-controlled
#                  in-flight span windows, one enter() per window)
#       "thread" — synchronous preadv per span (the portable fallback)
#       "mmap"   — map whole files, serve records as zero-copy views of the
#                  page cache (no pread syscalls at all; opt-in: span/byte
#                  accounting semantics differ from the pread backends)
#   REPRO_READER_DEPTH — target in-flight spans per uring submission window
#       (default 32; clamped to the ring size).  Higher depths help cold
#       NVMe / networked storage; on a warm page cache it mostly bounds
#       buffer residency.
#   REPRO_VERIFY_BACKEND — id-recompute/compare mode for VerifyBatcher:
#       "auto" (vectorized recompute, digest compare on TPU else string),
#       "vector", "process" (process-pool recompute off the GIL), "string" /
#       "digest" (per-record reference modes, combining disabled).


def reader_backend() -> str:
    return os.environ.get("REPRO_READER_BACKEND", "auto")


def reader_depth() -> int:
    return int(os.environ.get("REPRO_READER_DEPTH", "32"))


def verify_backend() -> str:
    return os.environ.get("REPRO_VERIFY_BACKEND", "auto")


def remat_policy():
    import jax

    if REMAT_POLICY == "names":
        return jax.checkpoint_policies.save_only_these_names(
            "attn_out", "ffn_out", "mixer_out"
        )
    return jax.checkpoint_policies.nothing_saveable


def residual_axes():
    return ("batch", "seq_sp", None) if SP_OUTPUTS else ("batch", "seq", None)


def scan_unroll():
    """Value to pass to lax.scan(unroll=...)."""
    return True if _UNROLL else 1


def unrolling() -> bool:
    return _UNROLL


@contextlib.contextmanager
def unroll_scans():
    global _UNROLL
    prev = _UNROLL
    _UNROLL = True
    try:
        yield
    finally:
        _UNROLL = prev
