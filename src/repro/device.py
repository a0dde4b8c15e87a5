"""Device policy and compile cache: the one place that asks JAX where it runs.

Every choice between a Pallas kernel and its host or XLA counterpart —
the store's digest probe, similarity scoring, the extraction verify, and
the kernels' own entry points — goes through :func:`on_tpu`.  It
asks JAX for its default backend (importing JAX if nothing has yet), so
the answer does not depend on what the caller happened to import first,
and an error in JAX's start-up surfaces instead of quietly selecting the
host path.

:func:`use_compile_cache` is what every entry point that compiles large
programs calls before its first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["on_tpu", "use_compile_cache", "COMPILE_CACHE_DIR"]

# <checkout>/.jax_cache: a fixed path, because the cache key includes it
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU (kernels run compiled)."""
    import jax

    return jax.default_backend() == "tpu"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache lives at the checkout's
    fixed ``.jax_cache`` directory.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
