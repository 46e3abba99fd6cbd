"""Edge aggregation with deadline-based straggler dropping (Eq. 3 / Eq. 6).

The masked-mean reduction itself lives in ``repro.kernels.masked_aggregate``
(one implementation shared by the jnp oracle, the Pallas kernel and this
edge path); this module owns the Eq. 6 effective-mask semantics and the
cloud-level aggregation.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.masked_aggregate.ops import masked_aggregate


def effective_mask(arrived: jax.Array, tau: jax.Array, z_min: int) -> jax.Array:
    """Eq. (6): use clients that arrived before the deadline; if fewer than Z
    arrived, wait for the Z fastest instead. arrived/tau: (C,). Returns fp32
    weights (C,)."""
    arrived = arrived.astype(jnp.float32)
    count = jnp.sum(arrived)
    # Z fastest by training time (selected clients only participate; callers
    # pass tau=+inf for unselected slots)
    z = min(int(z_min), arrived.shape[0])
    _, idx = jax.lax.top_k(-tau, z)
    fallback = jnp.zeros_like(arrived).at[idx].set(1.0)
    return jnp.where(count >= z, arrived, fallback)


def effective_mask_multi(arrived: jax.Array, tau: jax.Array,
                         valid: jax.Array, z_min: int) -> jax.Array:
    """Eq. 6 for all edge servers at once over fixed-capacity client slots.

    arrived/tau/valid: (M, S). ``valid`` marks real (selected) slots; padded
    slots are forced to arrived=0 / tau=+inf so the Z-fastest fallback ranks
    every real slot ahead of padding, and the final mask re-zeroes any
    padding the fallback still picked — reproducing the legacy per-ES
    ``min(z_min, C)`` clamp exactly (see tests/test_fed_batched.py).
    """
    valid = valid.astype(jnp.float32)
    arrived = arrived.astype(jnp.float32) * valid
    tau = jnp.where(valid > 0, tau, jnp.inf)
    w = jax.vmap(lambda a, t: effective_mask(a, t, z_min))(arrived, tau)
    return w * valid


def deadline_masked_aggregate(edge_params: Any, deltas: Any,
                              arrived: jax.Array, tau: jax.Array,
                              z_min: int = 1, use_kernel: bool = False,
                              tile: int = 512,
                              interpret: Optional[bool] = None
                              ) -> Tuple[Any, jax.Array]:
    """deltas: pytree with leading client axis (C, ...). Returns updated edge
    params (Eq. 3 restricted to the effective mask) + number of contributors.

    The reduction routes through the ``masked_aggregate`` ops wrapper so the
    edge path, the jnp oracle and the Pallas kernel share one implementation.
    """
    w = effective_mask(arrived, tau, z_min)
    out = masked_aggregate(edge_params, deltas, w, use_kernel=use_kernel,
                           tile=tile, interpret=interpret)
    return out, jnp.sum(w)


def cloud_aggregate(edge_params_stacked: Any) -> Any:
    """Global aggregation: mean over the leading edge-server axis."""
    return jax.tree.map(lambda a: jnp.mean(a, axis=0, dtype=a.dtype),
                        edge_params_stacked)


def broadcast_global(edge_params_stacked: Any) -> Any:
    """Every T_ES rounds each ES resets its edge model to the global mean."""
    def f(a):
        g = jnp.mean(a, axis=0, dtype=jnp.float32).astype(a.dtype)
        return jnp.broadcast_to(g[None], a.shape)
    return jax.tree.map(f, edge_params_stacked)
