#!/usr/bin/env python3
"""The program's own spans in a recorded trace, and the idle time under them.

    python bench/program_spans.py <trace.xplane.pb>

The program marks its layer boundaries with profiler spans
(``repro.trace.span``): ``service.*``, ``router.*``, ``store.*`` and
``engine.*``, each with its per-call values as event stats (``batch``,
``shard``, ``keys``, ``request``, ``step``).  They lie on the trace's
``/host:CPU`` plane, one line per thread, on the clock of the device
planes that :mod:`bench.tracing` reduces.  Here they are read beside
that reduction:

* :func:`breakdown`: :func:`bench.tracing.breakdown`, except that each
  idle gap goes to the innermost program span open at its middle (the
  deepest on its thread, ties to the latest start); only a gap under no
  program span falls back to the innermost ``bench.*`` span;
* :func:`summary`: the split of a lookup batch (per ``service.batch``:
  ``store.bloom``, ``store.probe``, ``store.verify``; per
  ``router.lookup``: its time outside the same batch's ``store.*``
  spans) and of the LM loop (``engine.prefill`` per admission,
  ``engine.emit`` per step), and the share of the device's idle time
  during which any program span was open.  A quantity whose spans the
  trace lacks is left out.

The command prints both for a trace recorded with the benchmark's window
markers (``bench.run.Tracer``), as one JSON object.
"""

from __future__ import annotations

import heapq
import json
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import tracing  # noqa: E402

__all__ = ["PREFIXES", "Span", "from_profile", "load", "breakdown",
           "idle_in_program", "per_span_ms", "mean_ms", "self_ms", "summary"]

PREFIXES = ("service.", "router.", "store.", "engine.")


@dataclass
class Span:
    name: str
    start: float          # seconds, trace clock
    end: float
    thread: int           # the host line (one per thread) it lies on
    stats: Dict[str, object] = field(default_factory=dict)
    depth: int = 0        # spans of its thread that enclose it

    @property
    def seconds(self) -> float:
        return self.end - self.start


def _nest(spans: List[Span]) -> List[Span]:
    """Set each span's depth on its thread; returns them by start."""
    by_thread: Dict[int, List[Span]] = defaultdict(list)
    for sp in spans:
        by_thread[sp.thread].append(sp)
    for line in by_thread.values():
        line.sort(key=lambda sp: (sp.start, -sp.end))
        open_: List[Span] = []
        for sp in line:
            while open_ and open_[-1].end < sp.end:
                open_.pop()
            sp.depth = len(open_)
            open_.append(sp)
    return sorted(spans, key=lambda sp: sp.start)


def from_profile(pd) -> List[Span]:
    """The program spans of a ``jax.profiler.ProfileData``."""
    out: List[Span] = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    s = e.start_ns * 1e-9
                    out.append(Span(e.name, s, s + e.duration_ns * 1e-9,
                                    thread, {k: v for k, v in e.stats}))
    return _nest(out)


def load(path: Path) -> List[Span]:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(str(path)))


def _open_at(spans: Sequence, times: Sequence[float], rank) -> List:
    """For each of the ascending ``times``, the span open there (start <=
    t <= end) that ``rank`` puts highest (ties to the later in start
    order), or None: one sweep."""
    order = sorted(spans, key=lambda sp: sp.start)
    live: list = []           # heap of (end, n, span)
    out, i = [], 0
    for t in times:
        while i < len(order) and order[i].start <= t:
            heapq.heappush(live, (order[i].end, i, order[i]))
            i += 1
        while live and live[0][0] < t:
            heapq.heappop(live)
        best = max(live, key=lambda x: (rank(x[2]), x[1]), default=None)
        out.append(best[2] if best is not None else None)
    return out


def _idle(trace: tracing.Trace) -> List[Tuple[float, float]]:
    return tracing.gaps([(o.start, o.end) for o in trace.ops[0]], trace.window)


def breakdown(trace: tracing.Trace, spans: Sequence[Span]) -> dict:
    """:func:`bench.tracing.breakdown` with idle gaps put down to program
    spans first (module docstring)."""
    idle_gaps = _idle(trace)
    mids = [(s + e) / 2 for s, e in idle_gaps]
    deepest = _open_at(spans, mids, lambda sp: (sp.depth, sp.start))
    # the benchmark's own rule: the latest-started bench span still open
    latest = _open_at(trace.spans, mids, lambda sp: sp.start)
    idle: Dict[str, float] = defaultdict(float)
    for (s, e), prog, bench in zip(idle_gaps, deepest, latest):
        sp = prog or bench
        idle[sp.name if sp is not None else "no bench span"] += e - s
    top = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": tracing.breakdown(trace)["device_ops"],
            "idle_gaps": [[k, v] for k, v in top]}


def _merged(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def idle_in_program(trace: tracing.Trace, spans: Sequence[Span]
                    ) -> Optional[float]:
    """Share of the window's device-idle time during which any program
    span was open, %."""
    idle = _idle(trace)
    total = sum(e - s for s, e in idle)
    if not spans or total <= 0:
        return None
    covered, j = 0.0, 0
    prog = _merged((sp.start, sp.end) for sp in spans)
    for s, e in idle:           # both lists ascending and disjoint
        while j < len(prog) and prog[j][1] <= s:
            j += 1
        k = j
        while k < len(prog) and prog[k][0] < e:
            covered += min(e, prog[k][1]) - max(s, prog[k][0])
            k += 1
    return 100.0 * covered / total


def _named(spans: Iterable[Span], name: str,
           window: Tuple[float, float]) -> List[Span]:
    return [sp for sp in spans
            if sp.name == name and window[0] <= sp.start < window[1]]


def per_span_ms(spans: Sequence[Span], window: Tuple[float, float],
                name: str, per: str) -> Optional[float]:
    """Summed time of the ``name`` spans over the number of ``per`` spans,
    of those that start in the window, ms."""
    mine, count = _named(spans, name, window), len(_named(spans, per, window))
    if not mine or not count:
        return None
    return 1e3 * sum(sp.seconds for sp in mine) / count


def mean_ms(spans: Sequence[Span], window: Tuple[float, float],
            name: str) -> Optional[float]:
    return per_span_ms(spans, window, name, name)


def self_ms(spans: Sequence[Span], window: Tuple[float, float],
            parent: str = "router.lookup", child: str = "store."
            ) -> Optional[float]:
    """Mean time of a ``parent`` span outside the spans named
    ``child*`` of the same batch, on any thread, ms."""
    parents = _named(spans, parent, window)
    if not parents:
        return None
    children: Dict[object, List[Tuple[float, float]]] = defaultdict(list)
    for sp in spans:
        if sp.name.startswith(child) and "batch" in sp.stats:
            children[sp.stats["batch"]].append((sp.start, sp.end))
    total = 0.0
    for p in parents:
        inside = tracing.union_length(children.get(p.stats.get("batch"), []),
                                      (p.start, p.end))
        total += p.seconds - inside
    return 1e3 * total / len(parents)


def summary(trace: tracing.Trace, spans: Sequence[Span]) -> Dict[str, float]:
    """The lookup and LM splits the trace has spans for (module docstring)."""
    w = trace.window
    out = {
        "router_self_ms": self_ms(spans, w),
        "bloom_ms": per_span_ms(spans, w, "store.bloom", "service.batch"),
        "probe_ms": per_span_ms(spans, w, "store.probe", "service.batch"),
        "upload_ms": per_span_ms(spans, w, "store.upload", "service.batch"),
        "verify_ms": per_span_ms(spans, w, "store.verify", "service.batch"),
        "batch_ms": mean_ms(spans, w, "service.batch"),
        "prefill_ms": mean_ms(spans, w, "engine.prefill"),
        "admit_ms": mean_ms(spans, w, "engine.admit"),
        "step_ms": mean_ms(spans, w, "engine.step"),
        "emit_ms": per_span_ms(spans, w, "engine.emit", "engine.step"),
        "idle_in_program": idle_in_program(trace, spans),
    }
    return {k: v for k, v in out.items() if v is not None}


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    path = Path(argv[0])
    trace, spans = tracing.load(path), load(path)
    print(json.dumps({"summary": summary(trace, spans),
                      "breakdown": breakdown(trace, spans)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
