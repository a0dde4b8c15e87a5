"""Reduce a profiler trace of the window to device metrics.

The trace is JAX's ``.xplane.pb``.  On a TPU each device plane
(``/device:TPU:<n>``) has an ``XLA Ops`` line, one event per operation
executed, named by its HLO text (``%name.N = <outputs> op(<operands>)``),
and an ``XLA Modules`` line, one event per program run.  Host spans are
the benchmark's own ``jax.profiler.TraceAnnotation`` events (``bench.*``)
on the ``/host:CPU`` plane, on the same clock.  The window is delimited
by two marker spans, ``bench.window_start`` and ``bench.window_stop``.

* busy: the union of the intervals in which an operation ran, clipped
  to the window, averaged over the devices;
* idle gaps: the complement of busy in the window, each attributed to the
  innermost ``bench.*`` span open at its middle;
* kernel time: the summed durations of a kernel's ``custom-call`` events
  (kernels are named by their jitted entry point, e.g.
  ``%sorted_probe_pallas.1 = ... custom-call(...)``), with the shapes of
  their operands and outputs parsed from the HLO text for the cost
  functions.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["Op", "Kernel", "Trace", "load", "union_length", "gaps",
           "breakdown", "parse_shapes"]

_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_HEAD = re.compile(r"^%([A-Za-z0-9_.\-]+?)(?:\.\d+)? = ")


@dataclass
class Op:
    name: str            # op name without its "%" and ".N" suffix
    start: float         # seconds, trace clock
    end: float
    text: str            # the HLO text the event is named by
    module: str = ""


@dataclass
class Kernel:
    """One run of a Pallas kernel: operand and output shapes, time."""

    name: str
    seconds: float
    outputs: List[Tuple[str, Tuple[int, ...]]]
    operands: List[Tuple[str, Tuple[int, ...]]]
    start: float = 0.0


@dataclass
class Trace:
    window: Tuple[float, float]
    ops: List[List[Op]]                 # per device
    modules: List[List[Op]]             # per device
    spans: List[Op]                     # host bench.* spans (not markers)
    kernels: List[Kernel] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        per = [union_length([(o.start, o.end) for o in ops], self.window)
               for ops in self.ops]
        return sum(per) / len(per) if per else 0.0

    def kernel_runs(self, name: str) -> List[Kernel]:
        return [k for k in self.kernels if k.name == name
                and self.window[0] <= k.start <= self.window[1]]


def parse_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """``[(dtype, dims), ...]`` of every typed shape in an HLO fragment."""
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


def union_length(intervals: Sequence[Tuple[float, float]],
                 window: Optional[Tuple[float, float]] = None) -> float:
    """Length of the union of ``intervals`` (clipped to ``window``)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if window is not None:
            s, e = max(s, window[0]), min(e, window[1])
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Sequence[Tuple[float, float]],
         window: Tuple[float, float]) -> List[Tuple[float, float]]:
    """The parts of ``window`` that no interval covers."""
    out, t = [], window[0]
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, window[1])))
        t = max(t, e)
        if t >= window[1]:
            break
    if t < window[1]:
        out.append((t, window[1]))
    return [(s, e) for s, e in out if e > s]


def _op_name(text: str) -> str:
    m = _HEAD.match(text)
    return m.group(1) if m else text.split(" ", 1)[0]


def _call_args(text: str, start: int) -> str:
    """The argument list of the call whose "(" is at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        depth += {"(": 1, ")": -1}.get(text[i], 0)
        if depth == 0:
            return text[start + 1: i]
    return text[start + 1:]


def _kernel(op: Op) -> Optional[Kernel]:
    if 'custom_call_target="tpu_custom_call"' not in op.text:
        return None
    at = op.text.find(" custom-call(")
    head = op.text[:at]
    operands = [shapes[0] for part in
                _call_args(op.text, at + len(" custom-call")).split(", ")
                if (shapes := parse_shapes(part))]
    return Kernel(op.name, op.end - op.start,
                  parse_shapes(head.partition(" = ")[2]), operands, op.start)


def load(path: Path, n_devices: int = 1,
         window: Optional[Tuple[float, float]] = None) -> Trace:
    """Read a trace; ``window`` overrides the marker spans (seconds)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops: List[List[Op]] = [[] for _ in range(n_devices)]
    modules: List[List[Op]] = [[] for _ in range(n_devices)]
    spans: List[Op] = []
    marks: Dict[str, float] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            if dev >= n_devices:
                continue
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is None:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    dest[dev].append(Op(_op_name(e.name) if dest is ops
                                        else e.name.split("(", 1)[0],
                                        s, s + e.duration_ns * 1e-9, e.name))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith("bench."):
                        continue
                    s = e.start_ns * 1e-9
                    if e.name in ("bench.window_start", "bench.window_stop"):
                        marks[e.name] = s
                    else:
                        spans.append(Op(e.name, s, s + e.duration_ns * 1e-9, e.name))
    if window is None:
        if len(marks) != 2:
            raise ValueError(f"trace lacks its window markers (found {sorted(marks)})")
        window = (marks["bench.window_start"], marks["bench.window_stop"])
    trace = Trace(window, ops, modules, spans)
    for dev in range(n_devices):
        ops[dev].sort(key=lambda o: o.start)
        modules[dev].sort(key=lambda o: o.start)
        starts = [m.start for m in modules[dev]]
        for op in ops[dev]:
            i = bisect.bisect_right(starts, op.start) - 1
            mod = modules[dev][i] if i >= 0 and op.start <= modules[dev][i].end else None
            op.module = mod.name if mod is not None else ""
            k = _kernel(op)
            if k is not None:
                trace.kernels.append(k)
    return trace


def _top(pairs: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(pairs.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(trace: Trace) -> dict:
    """Top device operations by time, and idle time by host span."""
    per_op: Dict[str, float] = defaultdict(float)
    for o in trace.ops[0]:
        s, e = max(o.start, trace.window[0]), min(o.end, trace.window[1])
        if e > s:
            module = o.module.removeprefix("jit_") or "?"
            per_op[f"{module}/{o.name}"] += e - s
    idle: Dict[str, float] = defaultdict(float)
    spans = sorted(trace.spans, key=lambda sp: sp.start)
    starts = [sp.start for sp in spans]
    for s, e in gaps([(o.start, o.end) for o in trace.ops[0]], trace.window):
        mid = (s + e) / 2
        label = "no bench span"
        # the latest-started span still open at the gap's middle
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if spans[i].end >= mid:
                label = spans[i].name
                break
        idle[label] += e - s
    return {"device_ops": _top(per_op), "idle_gaps": _top(idle)}
