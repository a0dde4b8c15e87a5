"""The tensor-parallel cell ``yi-6b-full.tp4.chat`` on four CPU devices.

A ``--rehearse`` run on four fake CPU devices (a subprocess, so that the
device count can be set before JAX starts) splits the tiny model over a
1x4 mesh and must read ``correct``; with the first token planted as the
least likely one it must not.  The collective reader is checked on
operations written as the chip's trace names them.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from test_bench_harness import SEED

ROOT = Path(__file__).resolve().parents[2]
CELL = "yi-6b-full.tp4.chat"

PROG = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [os.environ["ROOT"], os.path.join(os.environ["ROOT"], "src")]
    if os.environ["PLANT"] == "1":
        import jax.numpy as jnp
        from repro.serve.scheduler import ContinuousEngine

        def worst(self, logits, seed):  # the first token: the least likely one
            return int(jnp.argmin(logits[0]))

        ContinuousEngine._first_token = worst
    from bench import run
    sys.exit(run.main(sys.argv[1:]))
    """
)


def _run(tmp_path, plant: bool):
    env = dict(os.environ, ROOT=str(ROOT), PLANT="1" if plant else "0",
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", PROG, "--workload", CELL, "--seed", str(SEED),
         "--seconds", "1", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, env=env, timeout=600, cwd=ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_tp_cell_rehearses_on_four_devices(tmp_path):
    result = _run(tmp_path, plant=False)
    assert result["device"]["count"] == 4
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s", "ttft_p95_ms", "setup_s"}
    assert result["correct"] is True, result["checks"]


def test_tp_cell_least_likely_first_token_is_not_correct(tmp_path):
    result = _run(tmp_path, plant=True)
    assert result["correct"] is False
    gap = result["checks"]["served_token_logit_gap"]
    assert gap["value"] > gap["limit"]


def _reader(name):
    from bench.registry import Registry

    return Registry(ROOT).reader(name)


@pytest.mark.parametrize("text, collective", [
    ("%all-reduce.13 = bf16[64,1,4096]{2,0,1} all-reduce(bf16[64,1,4096]{2,0,1}"
     " %fusion.2), channel_id=19, replica_groups={{0,1,2,3}}", True),
    ("%all-gather-start.1 = (bf16[1,128,4096]{2,1,0}, bf16[1,512,4096]{2,1,0})"
     " all-gather-start(bf16[1,128,4096]{2,1,0} %bitcast.2), dimensions={1}", True),
    ("%fusion.125 = bf16[384,4096]{1,0} fusion(bf16[1536,4096]{1,0} %fusion.124),"
     " kind=kCustom, calls=%all-reduce-scatter.clone.clone", True),
    ("%custom-call.7 = bf16[1,1536,4096]{2,1,0} custom-call(bf16[1,384,4096]{2,1,0}"
     ' %param_0.361), custom_call_target="AsyncCollectiveDone"', True),
    ("%fusion.25 = bf16[64,4096]{1,0} fusion(bf16[64,4096]{1,0} %all-reduce.13),"
     " kind=kLoop, calls=%fused_computation.50", False),
    ("%copy-start.3 = (u32[256,2]{0,1}, u32[256,2]{0,1}, u32[]) copy-start("
     "u32[256,2]{0,1} %table.1)", False),
])
def test_collective_ops_are_known_by_their_trace_text(text, collective):
    from bench.tracing import Op, _op_name

    reader = _reader("collective_share.tp4")
    assert reader.is_collective(Op(_op_name(text), 0.0, 1.0, text)) is collective


def test_collective_share_is_over_busy_time_summed_over_devices():
    from types import SimpleNamespace

    from bench.tracing import Op, Trace

    ar = "%all-reduce.1 = bf16[8]{0} all-reduce(bf16[8]{0} %x)"
    mm = "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %x), kind=kLoop, calls=%f.1"
    dev0 = [Op("fusion", 0.0, 3.0, mm), Op("all-reduce", 3.0, 4.0, ar)]
    dev1 = [Op("fusion", 0.0, 1.0, mm), Op("all-reduce", 1.0, 4.0, ar)]
    trace = Trace((0.0, 10.0), [dev0, dev1], [[], []], [])
    share = _reader("collective_share.tp4").read(SimpleNamespace(trace_data=trace))
    assert share == pytest.approx(100.0 * 4.0 / 8.0)
