"""Sharding assembly: logical specs → NamedShardings for pjit boundaries."""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist.logical import current_rules, divisible_spec
from repro.launch.mesh import dp_axes

__all__ = [
    "abstract",
    "shardings_from_specs",
    "batch_shardings",
    "replicated",
]

PyTree = Any


def abstract(init, *args) -> Tuple[PyTree, PyTree]:
    """``init(*args) -> (arrays, logical specs)`` traced, not run:
    ``(ShapeDtypeStructs, specs)`` with nothing allocated."""
    box = {}

    def trace_me(*a):
        arrays, box["specs"] = init(*a)
        return arrays

    return jax.eval_shape(trace_me, *args), box["specs"]


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and all(s is None or isinstance(s, str) for s in x)


def shardings_from_specs(
    mesh: Mesh, specs: PyTree, shapes: Optional[PyTree] = None
) -> PyTree:
    """Map a pytree of logical-axis tuples to NamedShardings.

    With ``shapes`` (a matching pytree of arrays / ShapeDtypeStructs), mesh
    axes that don't divide the corresponding dim are dropped — and a
    ``kv_heads`` split that fails (GQA kv < model size) falls back to
    sharding the cache's sequence dim over "model" instead.
    """
    rules = current_rules()

    def one(spec, shape=None):
        p = rules.spec(spec, mesh)
        if shape is None:
            return NamedSharding(mesh, p)
        dims = shape.shape if hasattr(shape, "shape") else tuple(shape)
        q = divisible_spec(p, dims, mesh)
        # KV-cache fallback: if kv_heads couldn't take "model", give it to
        # the sequence dim (sequence-parallel KV — GSPMD inserts the
        # softmax partial reductions).
        if (
            "kv_heads" in spec
            and "model" in mesh.axis_names
            and all(pp != "model" for pp in q)
        ):
            ki = spec.index("kv_heads")
            parts = list(tuple(q) + (None,) * (len(dims) - len(tuple(q))))
            for j in range(ki + 1, len(dims)):
                if parts[j] is None and dims[j] % mesh.shape["model"] == 0 and dims[j] > 1:
                    parts[j] = "model"
                    break
            q = P(*parts)
        return NamedSharding(mesh, q)

    if shapes is None:
        return jax.tree_util.tree_map(one, specs, is_leaf=_is_spec)
    return jax.tree_util.tree_map(one, specs, shapes, is_leaf=_is_spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_shardings(mesh: Mesh, batch_specs: Dict[str, jax.ShapeDtypeStruct]):
    """Inputs: leading batch dim over dp axes, rest replicated.

    A batch dim that doesn't divide the dp extent (batch=1 long-context
    decode) is left replicated.
    """
    dp = dp_axes(mesh)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    dp_entry = dp if len(dp) > 1 else (dp[0] if dp else None)

    def one(sds):
        nd = len(sds.shape)
        if nd == 0 or sds.shape[0] % n_dp:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(dp_entry, *([None] * (nd - 1))))

    return {k: one(v) for k, v in batch_specs.items()}
