"""Mesh construction.

Functions, not module-level constants: importing this module must never
touch jax device state (a process that fakes host devices sets XLA_FLAGS
before jax first initializes).

Every mesh is built here, with Auto axes: the models place activations
with ``with_sharding_constraint`` (:func:`repro.dist.logical.constrain`),
which only accepts Auto axes, and ``jax.make_mesh`` defaults to Explicit
ones.  Activate a mesh with ``jax.set_mesh(mesh)``.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "mesh_from_str", "dp_axes"]


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None):
    """Arbitrary mesh (elastic re-carve after node loss, smoke meshes…)
    with Auto axes, over ``devices`` (default: all of them)."""
    if len(shape) != len(axes):
        raise ValueError(f"shape {shape} / axes {axes} mismatch")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def mesh_from_str(spec: str):
    """``"DATAxMODEL"`` → mesh, or None for the 1-device ``"1x1"`` case.

    The launchers' shared CLI surface: validates the shape string so a
    typo fails with the expected format instead of an unpack traceback.
    """
    parts = spec.lower().split("x")
    if len(parts) != 2 or not all(p.isdigit() for p in parts):
        raise ValueError(f"bad mesh {spec!r}; expected DATAxMODEL, e.g. 2x4")
    d, m = int(parts[0]), int(parts[1])
    if d < 1 or m < 1:
        raise ValueError(f"bad mesh {spec!r}; extents must be >= 1")
    if d * m == 1:
        return None
    return make_mesh((d, m), ("data", "model"))


def dp_axes(mesh) -> Tuple[str, ...]:
    """The data-parallel axes of a mesh (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")
