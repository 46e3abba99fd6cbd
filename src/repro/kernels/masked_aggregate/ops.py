"""jit'd public wrappers: pytree-level deadline-masked aggregation.

On TPU the Pallas kernel is used (interpret=False); this container is
CPU-only so ``use_kernel=True`` runs the same kernel body in interpret
mode while the default routes through the pure-jnp oracle. Three entry
points share one reduction implementation so the math cannot drift:

  * ``masked_aggregate_flat``   — single ES, pre-flattened (D,)/(C, D);
  * ``masked_aggregate``        — single ES, parameter pytree;
  * ``masked_aggregate_stacked``— all M edge servers at once: pytrees with
    a leading (M,) axis, deltas with (M, S) slot axes. Leaves are
    flattened and concatenated so each ES is one kernel launch over the
    whole parameter vector. Weights may also carry a leading seed axis
    (``(B, M, S)`` with params ``(B, M, ...)`` / deltas ``(B, M, S, ...)``,
    the fused multi-seed experiment engine's layout): seeds are folded
    into the ES axis so the whole sweep is one batched reduction.

``best_tile`` is the kernel's tile autotuner: callers that do not pin a
tile (``repro.fed.batched.make_engine``, ``benchmarks/kernels_bench.py``)
take its pick instead of a hardcoded 512.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.common import fastest
from repro.kernels.masked_aggregate.kernel import masked_aggregate_kernel
from repro.kernels.masked_aggregate.ref import (masked_aggregate_ref,
                                               masked_aggregate_ref_stacked)

DEFAULT_TILE = 512


@functools.lru_cache(maxsize=None)
def best_tile(param_count: int,
              candidates: Tuple[int, ...] = (256, 512, 1024, 2048)) -> int:
    """Pick the kernel tile by timing candidates on the current backend.

    Only meaningful where the compiled kernel actually runs (TPU): on
    other backends the jnp oracle is the fast path and interpret-mode
    timings say nothing about the lowered kernel, so the default tile is
    returned without timing. Cached per parameter count, so a process
    autotunes each model size once.
    """
    if jax.default_backend() != "tpu":
        return DEFAULT_TILE
    d = max(int(param_count), max(candidates))

    def inputs():
        c = 16
        return (jnp.zeros((d,), jnp.float32),
                jax.random.normal(jax.random.PRNGKey(0), (c, d),
                                  jnp.float32),
                jnp.ones((c,), jnp.float32))

    return fastest(candidates, inputs,
                   lambda tile, *a: masked_aggregate_kernel(
                       *a, tile=tile, interpret=False))


def masked_aggregate_flat(param: jax.Array, deltas: jax.Array,
                          weights: jax.Array, use_kernel: bool = False,
                          tile: int = 512,
                          interpret: Optional[bool] = None
                          ) -> jax.Array:
    """param: (D,); deltas: (C, D); weights: (C,). Returns (D,)."""
    if use_kernel:
        return masked_aggregate_kernel(param, deltas, weights, tile=tile,
                                       interpret=interpret)
    return masked_aggregate_ref(param, deltas, weights)


def masked_aggregate(edge_params: Any, deltas: Any, weights: jax.Array,
                     use_kernel: bool = False, tile: int = 512,
                     interpret: Optional[bool] = None) -> Any:
    """edge_params: pytree; deltas: same pytree with leading client axis (C,);
    weights: (C,) participation mask/weights."""
    leaves_p, treedef = jax.tree.flatten(edge_params)
    leaves_d = treedef.flatten_up_to(deltas)
    out = []
    for p, d in zip(leaves_p, leaves_d):
        c = d.shape[0]
        flat = masked_aggregate_flat(p.reshape(-1), d.reshape(c, -1), weights,
                                     use_kernel=use_kernel, tile=tile,
                                     interpret=interpret)
        out.append(flat.reshape(p.shape))
    return jax.tree.unflatten(treedef, out)


def masked_aggregate_stacked(edge_params: Any, deltas: Any,
                             weights: jax.Array, use_kernel: bool = False,
                             tile: int = 512,
                             interpret: Optional[bool] = None) -> Any:
    """Aggregate every edge server in one shot (batched HFL round hot spot).

    edge_params: pytree, leaves (M, ...); deltas: same pytree, leaves
    (M, S, ...) with S fixed-capacity client slots; weights: (M, S) —
    zero for padded/dropped slots. Each ES m gets Eq. 3 restricted to its
    mask with denominator max(sum_s w[m, s], 1). Leaves are concatenated
    along the flattened parameter axis so the reduction is one
    (S,)x(S, D_total) contraction per ES.

    With ``weights`` of rank 3 — ``(B, M, S)``, params ``(B, M, ...)``,
    deltas ``(B, M, S, ...)`` — the leading seed/batch axis is folded
    into the ES axis, every (seed, ES) pair aggregates under its own
    mask, and the result keeps the ``(B, M, ...)`` layout.
    """
    if weights.ndim == 3:
        b, m3, s3 = weights.shape
        leaves_p, treedef = jax.tree.flatten(edge_params)
        leaves_d = treedef.flatten_up_to(deltas)
        folded_p = jax.tree.unflatten(treedef, [
            p.reshape((b * m3,) + p.shape[2:]) for p in leaves_p])
        folded_d = jax.tree.unflatten(treedef, [
            d.reshape((b * m3, s3) + d.shape[3:]) for d in leaves_d])
        out = masked_aggregate_stacked(folded_p, folded_d,
                                       weights.reshape(b * m3, s3),
                                       use_kernel=use_kernel, tile=tile,
                                       interpret=interpret)
        return jax.tree.unflatten(treedef, [
            o.reshape(p.shape)
            for o, p in zip(treedef.flatten_up_to(out), leaves_p)])
    leaves_p, treedef = jax.tree.flatten(edge_params)
    leaves_d = treedef.flatten_up_to(deltas)
    m, s = weights.shape
    dims = [int(p.size) // m for p in leaves_p]
    flat_p = jnp.concatenate(
        [p.reshape(m, -1).astype(jnp.float32) for p in leaves_p], axis=1)
    flat_d = jnp.concatenate(
        [d.reshape(m, s, -1).astype(jnp.float32) for d in leaves_d], axis=2)
    if use_kernel:
        out = jnp.stack([
            masked_aggregate_kernel(flat_p[i], flat_d[i], weights[i],
                                    tile=tile, interpret=interpret)
            for i in range(m)])
    else:
        out = masked_aggregate_ref_stacked(flat_p, flat_d, weights)
    offsets = [sum(dims[:i]) for i in range(1, len(dims))]  # static splits
    pieces = jnp.split(out, offsets, axis=1)
    return jax.tree.unflatten(treedef, [
        piece.reshape(p.shape).astype(p.dtype)
        for piece, p in zip(pieces, leaves_p)])
