"""Pallas TPU kernel: deadline-masked weighted aggregation (HFL Eq. 3/6).

out[d] = param[d] + sum_c w[c] * delta[c, d] / max(sum_c w[c], 1)

This is the edge-aggregation hot spot: C client deltas of D flattened
parameters each, reduced under the participation mask. The kernel tiles D
into VMEM-resident blocks (C is small — tens of clients — and rides along
whole); the weighted reduction maps onto the MXU as a (1, C) x (C, TILE)
matmul. TILE is a multiple of 128 for lane alignment.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret


def _kernel(w_ref, denom_ref, delta_ref, param_ref, out_ref):
    w = w_ref[...].astype(jnp.float32)            # (1, C)
    d = delta_ref[...].astype(jnp.float32)        # (C, T)
    p = param_ref[...].astype(jnp.float32)        # (1, T)
    denom = denom_ref[0, 0]
    # HIGHEST: TPU's default f32 matmul rounds the deltas to bfloat16
    agg = jax.lax.dot(w, d, precision=jax.lax.Precision.HIGHEST) / denom
    out_ref[...] = (p + agg).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def masked_aggregate_kernel(param: jax.Array, deltas: jax.Array,
                            weights: jax.Array, tile: int = 512,
                            interpret: Optional[bool] = None) -> jax.Array:
    """param: (D,); deltas: (C, D); weights: (C,). Returns (D,)."""
    c, d = deltas.shape
    pad = (-d) % tile
    if pad:
        param = jnp.pad(param, (0, pad))
        deltas = jnp.pad(deltas, ((0, 0), (0, pad)))
    dp = param.shape[0]
    w2 = weights.reshape(1, c).astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(w2), 1.0).reshape(1, 1)
    out = pl.pallas_call(
        _kernel,
        grid=(dp // tile,),
        in_specs=[
            pl.BlockSpec((1, c), lambda i: (0, 0)),        # weights
            pl.BlockSpec((1, 1), lambda i: (0, 0)),        # denom
            pl.BlockSpec((c, tile), lambda i: (0, i)),     # deltas tile
            pl.BlockSpec((1, tile), lambda i: (0, i)),     # param tile
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, dp), param.dtype),
        interpret=resolve_interpret(interpret),
    )(w2, denom, deltas, param.reshape(1, dp))
    return out.reshape(dp)[:d]
