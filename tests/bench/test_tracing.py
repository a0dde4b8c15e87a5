"""The reduction from a device trace to metrics, and each kernel's costs.

The trace is a small recording from one TPU v5e chip
(``capture_trace.py``): three ``sorted_probe`` calls, one ``tanimoto``
top-k and one ``flash_attention``, each inside a ``bench.*`` span, in a
one-second window.  Each reduction is checked against a plain
recomputation from the raw trace events; each cost function against
shapes worked by hand.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import tracing  # noqa: E402
from bench.costs import nbytes, roofline_share  # noqa: E402
from bench.costs import dense_lm  # noqa: E402
from bench.costs import flash_attention as fa_cost  # noqa: E402
from bench.costs import sorted_probe_pallas as sp_cost  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402

TRACE = Path(__file__).with_name("data") / "v5e_small.xplane.pb"
V5E = peaks_for("TPU v5 lite")


# -- interval arithmetic ----------------------------------------------------


def test_union_and_gaps():
    iv = [(1.0, 2.0), (1.5, 3.0), (5.0, 6.0), (9.0, 12.0)]
    assert tracing.union_length(iv) == pytest.approx(2.0 + 1.0 + 3.0)
    assert tracing.union_length(iv, (0.0, 10.0)) == pytest.approx(4.0)
    assert tracing.gaps(iv, (0.0, 10.0)) == [(0.0, 1.0), (3.0, 5.0), (6.0, 9.0)]
    assert tracing.gaps([], (0.0, 1.0)) == [(0.0, 1.0)]


def test_idle_gaps_go_to_the_innermost_open_span():
    op = lambda s, e: tracing.Op("fusion", s, e, "%fusion.1 = f32[] fusion()")  # noqa: E731
    outer = tracing.Op("bench.chat", 0.0, 10.0, "bench.chat")
    inner = tracing.Op("bench.lookup", 2.0, 4.0, "bench.lookup")
    t = tracing.Trace((0.0, 10.0), [[op(1.0, 2.5), op(3.5, 6.0)]], [[]],
                      [outer, inner])
    assert t.busy_s == pytest.approx(4.0)
    idle = dict(map(tuple, tracing.breakdown(t)["idle_gaps"]))
    # (2.5, 3.5) lies inside bench.lookup; (0, 1) and (6, 10) only in bench.chat
    assert idle == pytest.approx({"bench.lookup": 1.0, "bench.chat": 5.0})


def test_parse_shapes():
    text = ("%flash_attention.1 = bf16[32,528,128]{2,1,0} custom-call("
            "bf16[32,528,128]{2,1,0} %pad.1, bf16[4,528,128]{2,1,0} %pad.2)")
    assert tracing.parse_shapes(text) == [
        ("bf16", (32, 528, 128)), ("bf16", (32, 528, 128)), ("bf16", (4, 528, 128))]


# -- the recorded trace -----------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(TRACE))
    return tracing.load(TRACE, 1), pd


def _raw(pd, line_name):
    for plane in pd.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == line_name:
                    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                             e.name) for e in line.events]
    return []


def test_recorded_window_busy_and_idle(recorded):
    t, pd = recorded
    assert 0.9 < t.window_s < 1.5
    lo, hi = t.window
    # busy: a plain sweep over the raw op events, clipped to the window
    ev = sorted((max(s, lo), min(e, hi)) for s, e, _ in _raw(pd, "XLA Ops")
                if min(e, hi) > max(s, lo))
    busy, end = 0.0, lo
    for s, e in ev:
        busy += max(0.0, e - max(s, end))
        end = max(end, e)
    assert t.busy_s == pytest.approx(busy, rel=1e-9)
    assert 0.0 < t.busy_s < t.window_s
    b = tracing.breakdown(t)
    idle = sum(v for _, v in b["idle_gaps"])
    assert idle <= t.window_s - t.busy_s + 1e-9
    labels = {k for k, _ in b["idle_gaps"]}
    assert labels <= {"no bench span", "bench.lookup", "bench.similar", "bench.chat"}
    assert "no bench span" in labels
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_recorded_kernels_and_their_time(recorded):
    t, pd = recorded
    runs = {n: t.kernel_runs(n) for n in
            ("sorted_probe_pallas", "tanimoto_blocks_pallas", "flash_attention")}
    assert len(runs["sorted_probe_pallas"]) == 3
    assert len(runs["flash_attention"]) == 1
    assert len(runs["tanimoto_blocks_pallas"]) >= 1
    raw = {}
    for s, e, name in _raw(pd, "XLA Ops"):
        head = name.split(" = ")[0].lstrip("%").rsplit(".", 1)[0]
        if 'custom_call_target="tpu_custom_call"' in name and t.window[0] <= s <= t.window[1]:
            raw[head] = raw.get(head, 0.0) + (e - s)
    for n, ks in runs.items():
        assert sum(k.seconds for k in ks) == pytest.approx(raw[n], rel=1e-9)
    assert fa_cost.is_flash(runs["flash_attention"][0])
    assert not any(fa_cost.is_flash(k) for k in runs["sorted_probe_pallas"])
    share = roofline_share(runs["sorted_probe_pallas"], sp_cost.cost, V5E)
    assert share is not None and 0.0 < share <= 100.0
    # the one flash call: 32 x 520 queries against 4 x 520 keys, 1 layer
    m = {"d_model": 4096, "n_heads": 32, "n_kv_heads": 4, "head_dim": 128,
         "n_layers": 1}
    flops, moved = fa_cost.prompt_cost(m, 520)
    least = max(flops / V5E.bf16_flops, moved / V5E.hbm_bytes_per_s)
    assert 0.0 < least <= runs["flash_attention"][0].seconds


# -- cost functions against hand-worked shapes ------------------------------


def _kernel(operands, outputs, seconds=1.0):
    return tracing.Kernel("k", seconds, outputs, operands)


def test_sorted_probe_cost():
    k = _kernel([("u32", (2, 32768)), ("u32", (64, 2, 64))],
                [("s32", (64, 1, 64)), ("s32", (64, 1, 64))])
    flops, moved = sp_cost.cost(k)
    assert flops is None
    assert moved == 2 * 32768 * 4 + 64 * 2 * 64 * 4 + 2 * 64 * 64 * 4


def test_decode_step_time_is_its_programs_runs_in_the_window():
    """``decode_mfu`` divides by the decode step's device time: the runs
    of the engine's ``jit_step`` program, clipped to the window."""
    from bench.registry import Registry

    decode_mfu = Registry(ROOT).reader("decode_mfu")
    mod = lambda name, s, e: tracing.Op(name, s, e, name)  # noqa: E731
    t = tracing.Trace((1.0, 3.0), [[]], [[
        mod("jit_step", 0.5, 1.5),      # half inside
        mod("jit__lambda", 1.5, 2.0),   # a prefill: not the decode step
        mod("jit_step", 2.0, 2.25),
        mod("jit_step", 2.9, 3.4),      # a tenth inside
    ]], [])
    assert decode_mfu.decode_seconds(t) == pytest.approx(0.5 + 0.25 + 0.1)


def test_flash_attention_cost_and_signature():
    m = {"d_model": 4096, "n_heads": 32, "n_kv_heads": 4, "head_dim": 128,
         "n_layers": 8}
    flops, moved = fa_cost.prompt_cost(m, 520)
    pairs = 520 * 521 // 2
    assert flops == 4 * 128 * 32 * pairs * 8
    # q and out: 32 heads; k and v: 4 heads; bf16; every layer
    assert moved == 2 * 520 * 128 * (2 * 32 + 2 * 4) * 8
    fa = _kernel([("bf16", (32, 528, 128)), ("bf16", (4, 528, 128)),
                  ("bf16", (4, 528, 128))], [("bf16", (32, 528, 128))])
    assert fa_cost.is_flash(fa)
    probe = _kernel([("u32", (2, 32768)), ("u32", (64, 2, 64))],
                    [("s32", (64, 1, 64)), ("s32", (64, 1, 64))])
    assert not fa_cost.is_flash(probe)


def test_dense_lm_flops():
    m = {"d_model": 8, "n_heads": 2, "n_kv_heads": 1, "head_dim": 4,
         "d_ff": 16, "vocab_size": 10, "n_layers": 3}
    w = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16     # q, k+v, o, SwiGLU
    assert dense_lm.prefill_flops(m, 5) == (2 * 5 * 3 * w + 4 * 4 * 2 * 3 * 15
                                            + 2 * 8 * 10)
    assert dense_lm.decode_flops(m, 7) == 2 * 3 * w + 2 * 8 * 10 + 4 * 4 * 2 * 3 * 7


def test_roofline_share_is_least_time_over_time():
    moved = nbytes([("f32", (1024, 1024))])
    k = _kernel([("f32", (1024, 1024))], [], seconds=moved / V5E.hbm_bytes_per_s)
    assert roofline_share([k], lambda _: (None, moved), V5E) == pytest.approx(100.0)
    k.seconds *= 4
    assert roofline_share([k], lambda _: (None, moved), V5E) == pytest.approx(25.0)
    # the matmul bound wins where it is larger
    assert roofline_share([k], lambda _: (V5E.bf16_flops * k.seconds, 0), V5E) \
        == pytest.approx(100.0)
    assert roofline_share([], lambda _: (None, 0), V5E) is None


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
