"""Closed-loop load: each client sends its next request when the last returns.

Copied from the program's ``repro.service.loadgen.run_closed_loop`` (one
barrier, each request timed from its send, percentiles over all
requests), with three changes the benchmark needs: every client walks a
request stream made in advance from the seed instead of drawing keys
itself; every request is kept as a record (send, done, answer) so the
answers can be checked after the window; and each call is wrapped in a
profiler span named after the request kind, so idle device time can be
attributed to what the host was doing.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Sequence

__all__ = ["Record", "Window", "closed_loop"]


@dataclass
class Record:
    client: int
    index: int            # position in the client's request stream
    t_send: float
    t_done: float
    answer: Any = None
    error: Optional[BaseException] = None


@dataclass
class Window:
    t_start: float
    t_end: float          # start + seconds: clients send nothing after it
    t_joined: float       # last client back
    records: List[Record]
    ramp_s: float = 0.0   # load offered before the window opened

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start


def closed_loop(
    call: Callable[[Any], Any],
    streams: Sequence[Sequence[Any]],
    seconds: float,
    span: str,
    on_start: Optional[Callable[[float], None]] = None,
    on_stop: Optional[Callable[[float], None]] = None,
    ramp_s: float = 0.0,
    join_timeout_s: float = 120.0,
) -> Window:
    """Drive ``call(request)`` from one thread per stream for ``seconds``.

    Client ``c`` sends ``streams[c][0]``, waits for the answer, sends
    ``streams[c][1]``, and so on (wrapping round at the end), until the
    window closes; a request in flight then finishes and is recorded.
    The window opens ``ramp_s`` after the clients start.  ``on_start(t)``
    and ``on_stop(t)`` run on the caller's thread as it opens and closes.
    """
    import jax

    clients = len(streams)
    barrier = threading.Barrier(clients + 1)
    stop = threading.Event()
    per_client: List[List[Record]] = [[] for _ in range(clients)]

    def client(c: int) -> None:
        stream = streams[c]
        mine = per_client[c]
        barrier.wait()
        i = 0
        while not stop.is_set():
            req = stream[i % len(stream)]
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(span):
                    ans = call(req)
            except Exception as e:  # noqa: BLE001 — a failed request is recorded
                mine.append(Record(c, i, t0, time.perf_counter(), error=e))
            else:
                mine.append(Record(c, i, t0, time.perf_counter(), ans))
            i += 1

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t_ramp = time.perf_counter()
    time.sleep(ramp_s)
    t_start = time.perf_counter()
    if on_start is not None:
        on_start(t_start)
    t_end = t_start + seconds
    time.sleep(max(0.0, t_end - time.perf_counter()))
    if on_stop is not None:
        on_stop(time.perf_counter())
    stop.set()
    for t in threads:
        t.join(timeout=join_timeout_s)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a client did not return within the join timeout")
    records = [r for rs in per_client for r in rs]
    return Window(t_start, t_end, time.perf_counter(), records,
                  t_start - t_ramp)
