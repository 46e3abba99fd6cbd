"""Pure-jnp oracle for the masked_aggregate kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def masked_aggregate_ref(param: jax.Array, deltas: jax.Array,
                         weights: jax.Array) -> jax.Array:
    w = weights.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(w), 1.0)
    agg = jnp.einsum("c,cd->d", w, deltas.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST) / denom
    return (param.astype(jnp.float32) + agg).astype(param.dtype)


def masked_aggregate_ref_stacked(params: jax.Array, deltas: jax.Array,
                                 weights: jax.Array) -> jax.Array:
    """Batched oracle over a leading edge-server axis.

    params: (M, D); deltas: (M, S, D); weights: (M, S). Returns (M, D) with
    each row aggregated under its own mask/denominator (max(sum w_m, 1))."""
    w = weights.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(w, axis=1), 1.0)
    # HIGHEST: TPU's default f32 matmul rounds the deltas to bfloat16
    agg = jnp.einsum("ms,msd->md", w, deltas.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return (params.astype(jnp.float32)
            + agg / denom[:, None]).astype(params.dtype)
