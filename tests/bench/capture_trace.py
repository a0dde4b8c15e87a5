#!/usr/bin/env python3
"""Record the small device trace that ``test_tracing.py`` reads (on a TPU).

    python tests/bench/capture_trace.py <out.xplane.pb>

Three ``sorted_probe`` calls, one ``tanimoto`` top-k and one
``flash_attention``, each inside a ``bench.*`` span, with host-only pauses
between them, traced by the harness's own :class:`bench.run.Tracer` over a
one-second window.  Every program is compiled before the window opens.
"""

from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.run import Tracer
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.sorted_probe.ops import sorted_probe
    from repro.kernels.tanimoto.ops import tanimoto_topk

    if jax.devices()[0].platform != "tpu":
        print("capture_trace: no TPU", file=sys.stderr)
        return 2
    rng = np.random.default_rng(0)
    table = np.sort(rng.integers(0, 2**32, (32768, 2), dtype=np.uint64), axis=0)
    table = jnp.asarray(table.astype(np.uint32))
    queries = table[::512]
    db = rng.integers(0, 2**32, (8192, 32), dtype=np.uint64).astype(np.uint32)
    q_fps = db[:8]
    q = jnp.ones((1, 32, 520, 128), jnp.bfloat16)
    kv = jnp.ones((1, 4, 520, 128), jnp.bfloat16)
    fa = jax.jit(flash_attention)

    def calls():
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.lookup"):
                jax.block_until_ready(sorted_probe(queries, table))
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.similar"):
            tanimoto_topk(q_fps, db, 10)
        time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.chat"):
            fa(q, kv, kv).block_until_ready()

    calls()                       # compile everything first
    tracer = Tracer(1.0)
    tracer.start()
    calls()
    tracer.join()
    dest = Path(out)
    dest.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(tracer.file(), dest)
    tracer.close()
    print(f"wrote {dest} ({dest.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
