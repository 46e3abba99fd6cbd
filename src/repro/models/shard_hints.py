"""Guarded with_sharding_constraint helpers.

Model code calls ``hint(x, spec...)`` at layout-critical points (logits,
MoE dispatch). Under a ``jax.sharding.set_mesh`` context the constraint is
applied with unavailable/non-divisible axes dropped; outside a mesh (CPU
smoke tests) it is a no-op.
"""
from __future__ import annotations

from typing import Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]


def _current_mesh():
    """The mesh set by ``jax.sharding.set_mesh``, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _filter_axis(axis: Axis, dim: int, mesh) -> Axis:
    names = tuple(axis) if isinstance(axis, tuple) else (axis,)
    keep = []
    size = 1
    for a in names:
        if a is None or a not in mesh.shape:
            continue
        if dim % (size * mesh.shape[a]) == 0:
            keep.append(a)
            size *= mesh.shape[a]
    if not keep:
        return None
    return keep[0] if len(keep) == 1 else tuple(keep)


def hint(x: jax.Array, *spec: Axis) -> jax.Array:
    """Best-effort sharding constraint; silently no-ops without a mesh."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    full = tuple(spec) + (None,) * (x.ndim - len(spec))
    filtered = tuple(_filter_axis(a, d, mesh)
                     for a, d in zip(full, x.shape))
    try:
        return jax.lax.with_sharding_constraint(x, P(*filtered))
    except Exception:  # noqa: BLE001 — never break functionality on hints
        return x


BATCH = ("pod", "data")
