"""Named logical-axis sharding: rule table, spec derivation, ``constrain``.

The models never mention mesh axes.  They speak in *logical* axis names —
"batch", "seq", "heads", "embed", "d_ff", … — both for parameter specs
(tuples returned next to params by every ``init_*``) and for activation
annotations (:func:`constrain` calls at layer boundaries).  This module
owns the translation:

* :class:`AxisRules` maps each logical name to the mesh axes it shards
  over (a name, a tuple of names for multi-axis groups like FSDP over
  ``("pod", "data")``, or ``None`` for replicated).
* :data:`DEFAULT_RULES` encodes the production layout: batch and the
  parameters' d_model dim over the data-parallel axes (FSDP/ZeRO-3),
  heads / d_ff / vocab / experts over "model" (tensor parallel), and the
  sequence-parallel residual layout ("seq_sp" → "model").
* :func:`axis_rules` is a context manager that swaps the active table —
  experiments override individual rules without touching model code.
* :func:`constrain` applies ``jax.lax.with_sharding_constraint`` with the
  spec the active rules produce **iff a mesh is active** (``jax.set_mesh``
  over a mesh from :mod:`repro.launch.mesh`); with no mesh it is the
  identity, so single-device smoke tests and the CPU container pay
  nothing.  Non-divisible dims degrade to replication (never an error).
* :func:`divisible_spec` is that degradation as a standalone helper — the
  launcher uses it when turning param/cache specs into NamedShardings.

Rules consult only ``mesh.axis_names`` / ``mesh.shape``, so a 1-device
smoke mesh, the 16×16 production pod and the 2×16×16 multi-pod mesh all
resolve from one table (absent axes drop out per rule).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec as P

__all__ = [
    "AxisRules",
    "DEFAULT_RULES",
    "axis_rules",
    "constrain",
    "current_rules",
    "divisible_spec",
]

# A rule's right-hand side: replicated, one mesh axis, or an ordered group
# of mesh axes (major → minor, e.g. FSDP over ("pod", "data")).
MeshAxes = Union[None, str, Tuple[str, ...]]


def _current_mesh() -> Optional[AbstractMesh]:
    """The mesh installed by ``jax.set_mesh``, or None outside any context.

    Works inside ``jit`` too, where the active mesh is abstract."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Immutable logical-name → mesh-axes table.

    The table is total over the names the models use; unknown names
    resolve to replicated (None) so adding a new logical axis in a model
    degrades gracefully until a rule is written for it.
    """

    table: Mapping[str, MeshAxes]

    def mesh_axes(self, logical: str, axis_names: Sequence[str]) -> MeshAxes:
        """Resolve one logical name against the axes a mesh actually has.

        Group rules keep only present axes — ("pod", "data") degrades to
        "data" on a single-pod mesh — and a rule with no surviving axis
        (or an unknown name) resolves to None (replicated).
        """
        want = self.table.get(logical)
        if want is None:
            return None
        if isinstance(want, str):
            want = (want,)
        present = tuple(a for a in want if a in tuple(axis_names))
        if not present:
            return None
        return present[0] if len(present) == 1 else present

    def spec(self, logical_axes: Sequence[Optional[str]], mesh: Any) -> P:
        """PartitionSpec for a tuple of logical names on ``mesh``.

        A mesh axis is consumed at most once per spec (GSPMD rejects
        duplicates): when two dims map to the same axis — ("d_ff",
        "vocab") both → "model" — the first dim keeps it and later dims
        drop it (replicated), matching the "first dim wins" convention of
        t5x/flax logical partitioning.
        """
        names = tuple(getattr(mesh, "axis_names", ()) or ())
        used: set = set()
        parts = []
        for logical in logical_axes:
            if logical is None:
                parts.append(None)
                continue
            axes = self.mesh_axes(logical, names)
            if axes is None:
                parts.append(None)
                continue
            group = (axes,) if isinstance(axes, str) else axes
            group = tuple(a for a in group if a not in used)
            if not group:
                parts.append(None)
                continue
            used.update(group)
            parts.append(group[0] if len(group) == 1 else group)
        return P(*parts)

    def extend(self, **overrides: MeshAxes) -> "AxisRules":
        """A new table with ``overrides`` replacing / adding rules."""
        merged = dict(self.table)
        merged.update(overrides)
        return AxisRules(table=merged)


# Production layout (DESIGN rationale in the module docstring):
#   dp / FSDP group  — batch and parameter d_model over ("pod", "data")
#   tensor parallel  — head-, ff-, vocab- and expert-sharded dims → "model"
#   sequence parallel— the residual's seq dim → "model" between TP regions
#   replicated       — per-layer stack dims, norm weights, tiny vectors
DEFAULT_RULES = AxisRules(
    table={
        # data-parallel / FSDP group
        "batch": ("pod", "data"),
        "embed": ("pod", "data"),
        # tensor-parallel dims
        "heads": "model",
        "kv_heads": "model",
        "d_ff": "model",
        "vocab": "model",
        "experts": "model",
        "conv_dim": "model",
        "ssm_heads": "model",
        # sequence-parallel residual layout (Megatron SP)
        "seq_sp": "model",
        # replicated
        "seq": None,
        "embed_act": None,
        "expert_ff": None,
        "layers": None,
        "block_pos": None,
        "frames": None,
    }
)


class _RuleStack(threading.local):
    def __init__(self):
        self.stack: list = []


_STACK = _RuleStack()


def current_rules() -> AxisRules:
    """The innermost :func:`axis_rules` table, or :data:`DEFAULT_RULES`."""
    return _STACK.stack[-1] if _STACK.stack else DEFAULT_RULES


@contextlib.contextmanager
def axis_rules(rules: Union[AxisRules, Mapping[str, MeshAxes]]):
    """Install a rule table for the dynamic extent of the block.

    Accepts a full :class:`AxisRules` or a mapping of overrides applied
    on top of the currently active table::

        with axis_rules({"seq_sp": None}):   # disable sequence parallelism
            loss = jax.jit(api.loss)(params, batch)
    """
    if not isinstance(rules, AxisRules):
        rules = current_rules().extend(**dict(rules))
    _STACK.stack.append(rules)
    try:
        yield rules
    finally:
        _STACK.stack.pop()


def _entry_divisible(entry: MeshAxes, dim: int, sizes: Mapping[str, int]) -> MeshAxes:
    """Shrink one spec entry until its axis-size product divides ``dim``.

    Group entries drop minor axes first (keep the longest divisible major
    prefix); a single axis either fits or is dropped entirely.
    """
    if entry is None:
        return None
    group = (entry,) if isinstance(entry, str) else tuple(entry)
    while group:
        n = 1
        for a in group:
            n *= int(sizes.get(a, 1))
        if n > 0 and dim % n == 0 and dim >= n:
            break
        group = group[:-1]
    if not group:
        return None
    return group[0] if len(group) == 1 else group


def divisible_spec(spec: Union[P, Sequence[Any]], shape: Sequence[int], mesh: Any) -> P:
    """Replication fallback: drop spec entries that don't divide the shape.

    ``spec`` entries are mesh-axis names (or axis groups) positionally
    matched with ``shape``; any dim whose assigned axes' total extent does
    not divide it falls back to None.  GSPMD would otherwise either pad or
    reject the sharding — for the tiny smoke configs that hit this path
    (12 heads on a model=16 mesh) replication is the correct degradation.
    """
    dims = tuple(shape)
    sizes = dict(getattr(mesh, "shape", {}) or {})
    entries = tuple(spec)[: len(dims)]
    parts = [
        _entry_divisible(entry, dims[i], sizes) for i, entry in enumerate(entries)
    ]
    return P(*parts)


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Annotate ``x`` with the sharding the active rules give these axes.

    Identity when no mesh is active (single-device paths trace exactly the
    same jaxpr they always did).  Under a mesh, resolves the logical names
    through :func:`current_rules`, degrades non-divisible dims to
    replication, and applies ``with_sharding_constraint``.  Fewer names
    than ``x.ndim`` leaves trailing dims unconstrained.
    """
    mesh = _current_mesh()
    if mesh is None:
        return x
    spec = current_rules().spec(logical_axes, mesh)
    spec = divisible_spec(spec, x.shape, mesh)
    if all(entry is None for entry in tuple(spec)):
        return x
    if isinstance(x, jax.core.Tracer):
        # under jit the active mesh is abstract: a bare spec resolves to it
        return jax.lax.with_sharding_constraint(x, spec)
    return jax.device_put(x, NamedSharding(jax.sharding.get_mesh(), spec))
