"""Robust Eq. 3 edge aggregation: trimmed mean, median, update clipping.

The paper's Eq. 3 is a participation-weighted mean over each edge
server's cohort — a single corrupted update (``FaultSpec.corrupt_rate``,
sign-flipped/scaled deltas) moves the edge model arbitrarily far. The
robust statistics literature's standard defenses are coordinate-wise
trimmed mean / median and norm clipping; this module layers them over
the ``masked_aggregate`` ops so the fused engines can swap the rule via
``TrainSpec(aggregator=...)`` without touching the round structure:

  * ``"mean"``         — the paper's rule, delegated verbatim to
                         ``masked_aggregate_stacked`` (bitwise the
                         historical path, kernel routing included);
  * ``"trimmed_mean"`` — per coordinate, drop the ``k`` lowest and ``k``
                         highest values among the cohort, mean the rest,
                         with ``k = min(max(1, floor(trim_frac * c)),
                         (c - 1) // 2)`` for cohorts of ``c >= 3`` (the
                         at-least-one-trim rule matters at the paper's
                         2-5-client cohorts) and ``k = 0`` below;
  * ``"median"``       — per-coordinate cohort median (mean of the two
                         middle order statistics for even ``c``);
  * ``"clipped"``      — each update's L2 norm is clipped to the cohort's
                         median valid norm, then Eq. 3's weighted mean —
                         bounding any single client's influence while
                         keeping honest updates intact.

All rules are pure jnp over the same flattened-parameter layout the ops
wrapper uses (leaves concatenated per ES, rank-3 ``(B, M, S)`` weights
folded into the ES axis), so they jit/vmap/scan inside the fused blocks
exactly like the mean path.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels.masked_aggregate.ops import masked_aggregate_stacked

AGGREGATORS = ("mean", "trimmed_mean", "median", "clipped")


def _sorted_valid(flat_d: jax.Array, valid: jax.Array) -> jax.Array:
    """Per-coordinate ascending sort with invalid slots pushed last.

    flat_d: (M, S, D); valid: (M, S) bool. Invalid slots sort as +inf and
    come back as 0 after the sort, so rank arithmetic over the first
    ``c`` positions sees only valid values.
    """
    keyed = jnp.where(valid[:, :, None], flat_d, jnp.inf)
    s = jnp.sort(keyed, axis=1)
    return jnp.where(jnp.isfinite(s), s, 0.0)


def _trimmed_mean(flat_d, valid, count, trim_frac: float):
    s = _sorted_valid(flat_d, valid)            # (M, S, D)
    c = count[:, None, None]                    # (M, 1, 1)
    k = jnp.where(c >= 3,
                  jnp.minimum(jnp.maximum(
                      1, jnp.floor(trim_frac * c).astype(jnp.int32)),
                      (c - 1) // 2),
                  0)
    ranks = jnp.arange(s.shape[1], dtype=jnp.int32)[None, :, None]
    keep = ((ranks >= k) & (ranks < c - k)).astype(jnp.float32)
    kept = jnp.maximum(jnp.sum(keep, axis=1), 1.0)   # (M, D) = c - 2k
    return jnp.sum(s * keep, axis=1) / kept


def _median(flat_d, valid, count):
    s = _sorted_valid(flat_d, valid)            # (M, S, D)
    c = count[:, None, None]
    lo = jnp.maximum((c - 1) // 2, 0)
    hi = jnp.maximum(c // 2, 0)
    v_lo = jnp.take_along_axis(s, jnp.broadcast_to(
        lo, (s.shape[0], 1, s.shape[2])), axis=1)[:, 0]
    v_hi = jnp.take_along_axis(s, jnp.broadcast_to(
        hi, (s.shape[0], 1, s.shape[2])), axis=1)[:, 0]
    return 0.5 * (v_lo + v_hi)                  # (M, D); 0 when c == 0


def _clipped_mean(flat_d, w, valid, count):
    norms = jnp.linalg.norm(flat_d, axis=2)     # (M, S)
    keyed = jnp.where(valid, norms, jnp.inf)
    s = jnp.sort(keyed, axis=1)
    s = jnp.where(jnp.isfinite(s), s, 0.0)
    c = count[:, None]
    lo = jnp.maximum((c - 1) // 2, 0)
    hi = jnp.maximum(c // 2, 0)
    med = 0.5 * (jnp.take_along_axis(s, lo, axis=1)
                 + jnp.take_along_axis(s, hi, axis=1))   # (M, 1)
    scale = jnp.minimum(1.0, med / jnp.maximum(norms, 1e-12))
    clipped = flat_d * scale[:, :, None]
    denom = jnp.maximum(jnp.sum(w, axis=1), 1.0)
    return jnp.einsum("ms,msd->md", w, clipped) / denom[:, None]


def robust_aggregate_stacked(edge_params: Any, deltas: Any,
                             weights: jax.Array, *,
                             aggregator: str = "mean",
                             trim_frac: float = 0.1,
                             use_kernel: bool = False, tile: int = 512,
                             interpret: Optional[bool] = None) -> Any:
    """Eq. 3 over all edge servers with a selectable aggregation rule.

    Same contract as ``masked_aggregate_stacked``: ``edge_params`` pytree
    with (M, ...) leaves, ``deltas`` (M, S, ...), ``weights`` (M, S)
    participation weights (0 for padded/dropped slots) — or the rank-3
    ``(B, M, S)`` fused multi-seed layout. ``aggregator="mean"`` is
    bitwise the ops wrapper (kernel routing included); the robust rules
    are jnp-only.
    """
    if aggregator == "mean":
        return masked_aggregate_stacked(edge_params, deltas, weights,
                                        use_kernel=use_kernel, tile=tile,
                                        interpret=interpret)
    if aggregator not in AGGREGATORS:
        raise ValueError(f"unknown aggregator {aggregator!r}; "
                         f"available: {AGGREGATORS}")
    if weights.ndim == 3:                        # fold (B, M, S) -> (B*M, S)
        b, m3, s3 = weights.shape
        leaves_p, treedef = jax.tree.flatten(edge_params)
        leaves_d = treedef.flatten_up_to(deltas)
        folded_p = jax.tree.unflatten(treedef, [
            p.reshape((b * m3,) + p.shape[2:]) for p in leaves_p])
        folded_d = jax.tree.unflatten(treedef, [
            d.reshape((b * m3, s3) + d.shape[3:]) for d in leaves_d])
        out = robust_aggregate_stacked(
            folded_p, folded_d, weights.reshape(b * m3, s3),
            aggregator=aggregator, trim_frac=trim_frac,
            use_kernel=use_kernel, tile=tile, interpret=interpret)
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
    w = weights.astype(jnp.float32)
    valid = w > 0
    count = jnp.sum(valid.astype(jnp.int32), axis=1)   # (M,)

    if aggregator == "trimmed_mean":
        agg = _trimmed_mean(flat_d, valid, count, float(trim_frac))
    elif aggregator == "median":
        agg = _median(flat_d, valid, count)
    else:                                        # "clipped"
        agg = _clipped_mean(flat_d, w, valid, count)
    # no-contributor edge servers keep their params (mean path: denom
    # clamp; here the sorted-clean values already sum to 0, but pin it
    # explicitly so every rule shares the c == 0 contract)
    agg = jnp.where(count[:, None] > 0, agg, 0.0)
    out = flat_p + agg

    offsets = [sum(dims[:i]) for i in range(1, len(dims))]  # static splits
    pieces = jnp.split(out, offsets, axis=1)
    return jax.tree.unflatten(treedef, [
        piece.reshape(p.shape).astype(p.dtype)
        for piece, p in zip(pieces, leaves_p)])


__all__ = ["AGGREGATORS", "robust_aggregate_stacked"]
