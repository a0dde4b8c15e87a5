#!/usr/bin/env python3
"""One run of one benchmark cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, system driver and per-layer metric
readers are found by name from ``BENCHMARK.json`` (see
:mod:`bench.registry`).  A run makes its data and weights from the seed,
warms every shape the cell uses (``setup_s``), measures for ``--seconds``,
then checks what the timed path produced against the configuration's
plain reference.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` records a device trace of the window and reports the
per-layer metrics.  The last line of standard output is one JSON object;
the numbers compared, each beside its limit, are the last lines of
standard error and the result's last key.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.  ``--rehearse`` runs on the CPU at tiny
sizes, for the tests only.  ``--control 1`` also reads, after the check,
the same numbers with the cell's control in the program's place (the
plain reference one precision down, or with one guarantee broken): the
readings that set the limits of ``correct``, under the result's
``control`` key.  The benchmark's own runs leave it off.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from bench.registry import Registry  # noqa: E402

# the traced part of a --trace 1 window (the rest of the window runs as usual)
TRACE_SECONDS = 8.0


class CompileCounter:
    """Counts programs lowered (compiled or loaded from the persistent
    cache) by listening to JAX's monitoring events."""

    def __init__(self):
        import jax

        self.lowered = 0
        self._lock = threading.Lock()

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                with self._lock:
                    self.lowered += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


class Tracer:
    """Device trace of the first ``seconds`` of the window."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.dir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
        self._timer: Optional[threading.Timer] = None
        self.hooks_start: List = []
        self.hooks_stop: List = []

    def start(self, _t=None) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1    # user spans (TraceAnnotation) only
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window_start"):
            pass
        for h in self.hooks_start:
            h()
        self._timer = threading.Timer(self.seconds, self.stop)
        self._timer.start()

    def stop(self) -> None:
        import jax

        for h in self.hooks_stop:
            h()
        with jax.profiler.TraceAnnotation("bench.window_stop"):
            pass
        jax.profiler.stop_trace()

    def join(self) -> None:
        if self._timer is not None:
            self._timer.join()

    def file(self) -> Path:
        found = sorted(self.dir.rglob("*.xplane.pb"))
        if not found:
            raise FileNotFoundError("the profiler wrote no trace")
        return found[-1]

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


class Context:
    """What a system driver and a metric reader may read about the run."""

    def __init__(self, cell, args, peaks, devices, rehearse: bool):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.kind = None
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearse = rehearse
        self.peaks = peaks
        self.devices = devices
        # filled as the run goes
        self.state = None
        self.system = None
        self.trace_data = None
        self.window_compiles = 0
        self.reference = None


def _fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control's numbers after the check")
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: CPU, tiny sizes")
    args = ap.parse_args(argv)

    registry = Registry(root, rehearse=args.rehearse)
    cell = registry.cell(args.workload)
    system = registry.system(cell)
    traffic_kind = registry.kind(cell)
    reference = registry.reference(cell)
    readers = {m["name"]: registry.reader(m["name"]) for m in cell.per_layer}

    import jax

    # a persistent cache that keeps every compile, even sub-second kernels
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.device import use_compile_cache

    use_compile_cache()
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not args.rehearse:
        if platform != "tpu":
            return _fail(f"no TPU: JAX platform is {platform!r}; refusing to run")
        if len(devices) < cell.chips:
            return _fail(f"{cell.name} needs {cell.chips} chips, found {len(devices)}")
    from bench.peaks import peaks_for

    peaks = None if args.rehearse else peaks_for(kind)
    ctx = Context(cell, args, peaks, devices[: cell.chips], args.rehearse)
    ctx.system, ctx.kind, ctx.reference = system, traffic_kind, reference
    compiles = CompileCounter()

    t0 = time.perf_counter()
    state = system.setup(ctx)
    setup_s = time.perf_counter() - t0
    ctx.state = state

    tracer = Tracer(min(TRACE_SECONDS, ctx.seconds)) if ctx.trace else None
    if tracer is not None:
        tracer.hooks_start.append(lambda: system.trace_mark(ctx, state, "start"))
        tracer.hooks_stop.append(lambda: system.trace_mark(ctx, state, "stop"))
    lowered_before = compiles.lowered
    try:
        system.measure(ctx, state, on_start=tracer.start if tracer else None)
        if tracer is not None:
            tracer.join()
        ctx.window_compiles = compiles.lowered - lowered_before
        peaks_in_use = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                        for d in ctx.devices]
        memory_peak = max((p for p in peaks_in_use if p is not None),
                          default=None)
        e2e = system.end_to_end(ctx, state)
        attempted, failed = system.attempted_failed(state)
        breakdown = None
        if tracer is not None:
            from bench import tracing

            ctx.trace_data = tracing.load(tracer.file(), len(ctx.devices))
            breakdown = tracing.breakdown(ctx.trace_data)
        t_check = time.perf_counter()
        checks = system.check(ctx, state, reference)
        check_s = time.perf_counter() - t_check
        control = system.control(ctx, state, reference) if args.control else []
    finally:
        if tracer is not None:
            tracer.close()

    checks.append(("failed_requests", failed, 0))
    correct = all(v <= lim for _, v, lim in checks)
    metrics: Dict[str, dict] = {}
    if not ctx.trace:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        # load offered before the window (a ramp to steady state) is set-up
        values = dict(e2e, setup_s=setup_s + state.timings.get("ramp_s", 0.0))
        for name, unit in units.items():
            metrics[name] = {"value": values[name], "unit": unit}
    else:
        for m in cell.per_layer:
            v = readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": platform, "kind": kind, "count": len(ctx.devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if ctx.trace_data is not None:
        device["busy_s"] = ctx.trace_data.busy_s
        device["window_s"] = ctx.trace_data.window_s
        result["breakdown"] = breakdown
    result["setup_detail"] = dict(state.timings, setup_s=setup_s, check_s=check_s,
                                  window_compiles=ctx.window_compiles)
    if args.control:
        result["control"] = {n: {"value": v, "limit": lim} for n, v, lim in control}
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    for n, v, lim in control:
        print(f"control {n}: {v} (limit {lim})", file=sys.stderr)
    for n, v, lim in checks:
        print(f"check {n}: {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
