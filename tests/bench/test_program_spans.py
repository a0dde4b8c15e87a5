"""The program's own spans and counters, and their reduction beside the trace.

A real profiler trace is recorded on the CPU backend around a few
coalesced lookup batches through a small ``QueryService`` (device probe
forced, so the jnp reference runs where the kernel would) and a few steps
of a tiny ``ContinuousEngine`` (``capture_program_trace.record``).  The
reduction (:mod:`bench.program_spans`) is checked on hand-built traces,
on the recorded v5e trace without program spans (``v5e_small``), and on
one recorded with them on a v5e chip (``v5e_program``).
"""

from __future__ import annotations

import sys
from collections import defaultdict
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import capture_program_trace as capture  # noqa: E402
from bench import program_spans as ps  # noqa: E402
from bench import tracing  # noqa: E402

DATA = Path(__file__).with_name("data")
LOOKUP_SPANS = {"service.batch", "router.lookup", "router.shard", "store.bloom",
                "store.probe", "store.upload", "store.verify"}
LM_SPANS = {"engine.admit", "engine.prefill", "engine.step", "engine.emit"}


@pytest.fixture(scope="module")
def cpu_run(tmp_path_factory):
    got = capture.record(tmp_path_factory.mktemp("program_trace"))
    trace = tracing.load(got["file"], 1)
    lo, hi = trace.window
    spans = [sp for sp in ps.load(got["file"]) if lo <= sp.start <= hi]
    return got, spans


def _encloses(outer, inner) -> bool:
    return outer.start <= inner.start and inner.end <= outer.end


def _parent(spans, sp, name):
    """The ``name`` span on ``sp``'s thread that encloses it, one level up."""
    found = [p for p in spans if p.name == name and p.thread == sp.thread
             and p.depth == sp.depth - 1 and _encloses(p, sp)]
    assert len(found) == 1, (sp, found)
    return found[0]


# -- the spans the program records -------------------------------------------


def test_every_program_span_is_recorded_and_nested(cpu_run):
    _, spans = cpu_run
    assert {sp.name for sp in spans} == LOOKUP_SPANS | LM_SPANS
    for sp in spans:
        if sp.name == "router.lookup":
            _parent(spans, sp, "service.batch")
        elif sp.name == "store.upload":
            _parent(spans, sp, "store.probe")
        elif sp.name == "engine.prefill":
            _parent(spans, sp, "engine.admit")
        elif sp.name in ("service.batch", "engine.admit", "engine.step",
                         "engine.emit"):
            assert sp.depth == 0, sp


def test_spans_of_one_batch_share_its_id(cpu_run):
    _, spans = cpu_run
    batches = {sp.stats["batch"]: sp for sp in spans if sp.name == "service.batch"}
    assert len(batches) >= 2
    threads = defaultdict(set)
    for sp in spans:
        if sp.name in LOOKUP_SPANS:
            outer = batches[sp.stats["batch"]]
            assert _encloses(outer, sp), sp
            threads[sp.stats["batch"]].add(sp.thread)
    gathered = [sp for sp in spans if sp.name == "router.shard"]
    assert gathered, "no batch scattered over the gather threads"
    for sp in gathered:
        assert sp.thread != batches[sp.stats["batch"]].thread
    assert any(len(t) > 1 for t in threads.values())


def test_span_stats_carry_the_per_call_values(cpu_run):
    got, spans = cpu_run
    for sp in spans:
        if sp.name == "service.batch":
            assert sp.stats["keys"] >= sp.stats["requests"] >= 1
            assert sp.stats["reason"] in ("full", "cohort", "deadline",
                                          "immediate")
        elif sp.name in ("router.shard", "store.probe"):
            assert 0 <= sp.stats["shard"] < capture.SHARDS
        elif sp.name == "store.upload":
            assert sp.stats["rows"] == capture.KEYS // capture.SHARDS
        elif sp.name == "engine.prefill":
            admit = _parent(spans, sp, "engine.admit")
            assert sp.stats["request"] == admit.stats["request"]
            assert sp.stats["bucket"] >= sp.stats["prompt_len"]
    steps = sorted(sp.stats["step"] for sp in spans if sp.name == "engine.step")
    emits = sorted(sp.stats["step"] for sp in spans if sp.name == "engine.emit")
    assert steps == emits and steps == list(range(steps[0], steps[0] + len(steps)))
    assert {sp.stats["request"] for sp in spans if sp.name == "engine.admit"} \
        == set(range(len(capture.PROMPTS) * 2 + 1, len(capture.PROMPTS) * 3 + 1))


def test_counters_advance_with_the_spans(cpu_run):
    got, spans = cpu_run
    before, after = got["before"], got["after"]
    store = {k: after["service"]["store"][k] - before["service"]["store"][k]
             for k in ("upload_bytes", "device_probes")}
    uploads = [sp for sp in spans if sp.name == "store.upload"]
    # (hi, lo) uint32 pairs: 8 bytes a table row and a query
    assert store["upload_bytes"] == sum(
        8 * (sp.stats["rows"] + sp.stats["keys"]) for sp in uploads)
    assert store["device_probes"] == len(uploads) > 0
    sched = {k: after["service"]["scheduler"][k] - before["service"]["scheduler"][k]
             for k in ("requests_flushed", "queue_wait_s", "batches")}
    assert sched["requests_flushed"] == capture.CLIENTS * capture.CALLS
    assert sched["batches"] == sum(sp.name == "service.batch" for sp in spans)
    assert sched["queue_wait_s"] >= 0.0
    eng = {k: after["engine"][k] - before["engine"][k]
           for k in ("prefills", "steps", "queue_wait_s")}
    assert eng["prefills"] == len(capture.PROMPTS)
    assert eng["steps"] == sum(sp.name == "engine.step" for sp in spans)
    assert eng["queue_wait_s"] > 0.0   # the second and third waited for a prefill


# -- the reduction, on hand-built traces --------------------------------------


def _op(s, e):
    return tracing.Op("fusion", s, e, "%fusion.1 = f32[] fusion()")


def _span(name, s, e, thread, **stats):
    return ps.Span(name, s, e, thread, stats)


def test_gap_goes_to_the_deepest_program_span_else_the_bench_rule():
    bench = [tracing.Op("bench.lookup", 0.0, 10.0, "bench.lookup")]
    t = tracing.Trace((0.0, 10.0), [[_op(1.0, 2.0), _op(4.0, 5.0), _op(7.0, 8.0)]],
                      [[]], bench)
    spans = ps._nest([
        # thread 0: a batch with a probe inside, over the first gap
        _span("service.batch", 0.6, 3.9, 0, batch=1),
        _span("router.lookup", 0.7, 3.8, 0, batch=1),
        # thread 1: a deeper chain over the same gap
        _span("router.shard", 0.8, 3.7, 1, batch=1),
        _span("store.probe", 0.85, 3.6, 1, batch=1),
        _span("store.upload", 2.5, 3.5, 1, batch=1),
        # the second gap: one shallow span on each thread, thread 1's later
        _span("engine.step", 5.5, 6.8, 0),
        _span("engine.emit", 5.6, 6.9, 1),
    ])
    assert [sp.depth for sp in spans if sp.name == "store.upload"] == [2]
    idle = dict(map(tuple, ps.breakdown(t, spans)["idle_gaps"]))
    # (0, 1): none open at 0.5 but bench; (2, 4): upload (depth 2) at 3.0;
    # (5, 7): both depth 0 at 6.0, the later start wins; (8, 10): bench
    assert idle == pytest.approx({"bench.lookup": 1.0 + 2.0,
                                  "store.upload": 2.0, "engine.emit": 2.0})
    # 0.6-1 of the first gap, 2-3.9, 5.5-6.9 are under a program span
    assert ps.idle_in_program(t, spans) == pytest.approx(
        100 * (0.4 + 1.9 + 1.4) / 7.0)


def test_router_self_time_subtracts_its_batch_children_on_any_thread():
    spans = [
        _span("router.lookup", 0.0, 10.0, 0, batch=1),
        _span("store.bloom", 1.0, 2.0, 0, batch=1),
        _span("store.probe", 3.0, 7.0, 1, batch=1),      # gather thread
        _span("store.probe", 6.0, 8.0, 2, batch=1),      # overlaps the last
        _span("store.probe", 2.0, 9.0, 3, batch=2),      # another batch
        _span("router.lookup", 20.0, 24.0, 0, batch=2),
    ]
    w = (0.0, 30.0)
    # batch 1: 10 - (1 + 5); batch 2: its child lies outside it, 4 - 0
    assert ps.self_ms(spans, w) == pytest.approx(1e3 * (4.0 + 4.0) / 2)
    assert ps.per_span_ms(spans, w, "store.probe", "router.lookup") == \
        pytest.approx(1e3 * 13.0 / 2)
    assert ps.mean_ms(spans, w, "router.lookup") == pytest.approx(1e3 * 7.0)


def test_readings_are_none_without_their_spans():
    t = tracing.Trace((0.0, 1.0), [[_op(0.2, 0.4)]], [[]], [])
    w = t.window
    assert ps.self_ms([], w) is None
    assert ps.per_span_ms([], w, "store.bloom", "service.batch") is None
    assert ps.per_span_ms([_span("store.bloom", 0.1, 0.2, 0)], w,
                          "store.bloom", "service.batch") is None
    assert ps.mean_ms([], w, "engine.prefill") is None
    assert ps.idle_in_program(t, []) is None
    assert ps.summary(t, []) == {}


def test_breakdown_is_the_benchmarks_where_no_program_span_was_recorded():
    path = DATA / "v5e_small.xplane.pb"
    trace = tracing.load(path, 1)
    spans = ps.load(path)
    assert spans == []
    assert ps.breakdown(trace, spans) == tracing.breakdown(trace)


# -- the recorded v5e trace with program spans --------------------------------


@pytest.fixture(scope="module")
def chip_run():
    path = DATA / "v5e_program.xplane.pb"
    trace = tracing.load(path, 1)
    return trace, ps.load(path)


def test_program_spans_lie_on_the_device_clock(chip_run):
    """Every ``sorted_probe`` kernel runs inside a ``store.probe`` span,
    after that span's ``store.upload``, once the device events are moved
    by one fixed offset: on this capture they lead the host spans by
    1.0-2.4 ms, and any single offset in that range places all of them."""
    trace, spans = chip_run
    kernels = trace.kernel_runs("sorted_probe_pallas")
    probes = [(next(u for u in spans if u.name == "store.upload"
                    and u.thread == p.thread and _encloses(p, u)), p)
              for p in spans if p.name == "store.probe"]
    assert len(kernels) == len(probes) > 0

    def placed(offset: float) -> int:
        return sum(any(u.end <= k.start + offset and
                       k.start + k.seconds + offset <= p.end
                       for u, p in probes) for k in kernels)

    fits = [us for us in range(0, 3000, 100) if placed(us * 1e-6) == len(kernels)]
    assert fits and fits[0] <= 1000 and fits[-1] >= 2000
    # the first token of every prefill comes back inside its span
    argmax = [m for m in trace.modules[0] if m.name == "jit__argmax"]
    for p in (sp for sp in spans if sp.name == "engine.prefill"):
        assert any(p.start <= m.start + 1e-3 <= p.end for m in argmax)


def test_recorded_idle_gaps_go_to_program_spans(chip_run):
    trace, spans = chip_run
    assert {sp.name for sp in spans} == LOOKUP_SPANS | LM_SPANS
    base, ours = tracing.breakdown(trace), ps.breakdown(trace, spans)
    assert ours["device_ops"] == base["device_ops"]
    assert not any(k.startswith(ps.PREFIXES) for k, _ in base["idle_gaps"])
    idle = trace.window_s - trace.busy_s
    under = sum(v for k, v in ours["idle_gaps"] if k.startswith(ps.PREFIXES))
    assert under >= 0.8 * idle
    assert ps.idle_in_program(trace, spans) >= 90.0
