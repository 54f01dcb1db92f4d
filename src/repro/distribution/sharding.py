"""Mesh-aware sharding annotations that degrade gracefully.

Model code calls ``constrain(x, "data", None, "model")`` at layout-critical
points.  Under an active mesh (``jax.sharding.set_mesh``) this lowers to a
real ``with_sharding_constraint``; in single-device tests it is a no-op.
Axis names not present on the current mesh are dropped, so the same model
code runs on ``("data","model")`` and ``("pod","data","model")`` meshes.

Axis conventions:
  * ``data``  — batch / tokens (and ZeRO-sharded optimizer state)
  * ``model`` — heads / ffn / experts / vocab (tensor & expert parallelism)
  * ``pod``   — pods; in the federated mapping, one pod = one hospital silo
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
from jax.sharding import PartitionSpec as P


AxisLike = Any  # None | str | tuple[str, ...]

DATA = "data"
MODEL = "model"
POD = "pod"


def active_mesh():
    """The mesh set by ``jax.sharding.set_mesh``, or None outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    return mesh


def clean_spec(spec: Sequence[AxisLike] | P) -> P | None:
    """Drop axis names that do not exist on the active mesh."""
    mesh = active_mesh()
    if mesh is None:
        return None
    names = set(mesh.axis_names)

    def _clean(axis: AxisLike) -> AxisLike:
        if axis is None:
            return None
        if isinstance(axis, (tuple, list)):
            kept = tuple(a for a in axis if a in names)
            return kept if kept else None
        return axis if axis in names else None

    return P(*(_clean(a) for a in spec))


def constrain(x: jax.Array, *spec: AxisLike) -> jax.Array:
    """``with_sharding_constraint`` against the active mesh (no-op without one)."""
    cleaned = clean_spec(spec)
    if cleaned is None:
        return x
    return jax.lax.with_sharding_constraint(x, cleaned)


def named_sharding(mesh, *spec: AxisLike):
    from jax.sharding import NamedSharding

    names = set(mesh.axis_names)

    def _clean(axis):
        if axis is None:
            return None
        if isinstance(axis, (tuple, list)):
            kept = tuple(a for a in axis if a in names)
            return kept if kept else None
        return axis if axis in names else None

    return NamedSharding(mesh, P(*(_clean(a) for a in spec)))
