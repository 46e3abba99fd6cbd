"""Pallas TPU kernel: RWKV6 WKV chunked linear recurrence (forward).

Per (batch, head) grid cell the kernel walks the sequence in VMEM-resident
chunks, carrying the (dk x dv) state in scratch. Within a chunk the exclusive
(RWKV) convention is used:

  y_t = r_t . C_{t-1} + (r_t . (u o k_t)) v_t
  C_t = diag(w_t) C_{t-1} + k_t v_t^T,   w_t = exp(log_w_t) in (0, 1]

The intra-chunk part is two (C x C) / (C x dk) matmuls (MXU-friendly); the
inter-chunk state update is rank-C. Chunk size 64 keeps exp(+-cumlog) in
fp32 range for realistic decays.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, fin_ref, state_scr, *,
            chunk: int, num_chunks: int):
    state_scr[...] = jnp.zeros_like(state_scr)
    u = u_ref[0].astype(jnp.float32)                       # (dk,)

    def body(c, _):
        sl = pl.dslice(c * chunk, chunk)
        r = r_ref[0, 0, sl, :].astype(jnp.float32)         # (C, dk)
        k = k_ref[0, 0, sl, :].astype(jnp.float32)
        v = v_ref[0, 0, sl, :].astype(jnp.float32)         # (C, dv)
        lw = lw_ref[0, 0, sl, :].astype(jnp.float32)
        lcum = jnp.cumsum(lw, axis=0)                      # inclusive
        ltot = lcum[-1:, :]                                # (1, dk)
        q_t = r * jnp.exp(lcum - lw)                       # exclusive decay
        k_adj = k * jnp.exp(-lcum)
        scores = jax.lax.dot_general(q_t, k_adj,
                                     (((1,), (1,)), ((), ())))  # (C, C)
        ii = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(jj < ii, scores, 0.0)           # strictly lower
        y = jax.lax.dot(scores, v)
        state = state_scr[...]                             # (dk, dv)
        y = y + jax.lax.dot(q_t, state)
        bonus = jnp.sum(r * u[None, :] * k, axis=1, keepdims=True)
        y = y + bonus * v
        ksum = k * jnp.exp(ltot - lcum)                    # (C, dk)
        state_scr[...] = (state * jnp.exp(ltot).T
                          + jax.lax.dot_general(
                              ksum, v, (((0,), (0,)), ((), ()))))
        y_ref[0, 0, sl, :] = y.astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, num_chunks, body, 0)
    fin_ref[0, 0] = state_scr[...].astype(fin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan_kernel(r: jax.Array, k: jax.Array, v: jax.Array,
                      log_w: jax.Array, u: jax.Array, chunk: int = 64,
                      interpret: Optional[bool] = None):
    """r,k,log_w: (B,H,T,dk); v: (B,H,T,dv); u: (H,dk).
    Returns (y (B,H,T,dv) fp32, final_state (B,H,dk,dv) fp32)."""
    b, h, t, dk = r.shape
    dv = v.shape[-1]
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    nc = t // chunk
    kernel = functools.partial(_kernel, chunk=chunk, num_chunks=nc)
    y, fin = pl.pallas_call(
        kernel,
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, t, dk), lambda bb, hh: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, t, dk), lambda bb, hh: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, t, dv), lambda bb, hh: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, t, dk), lambda bb, hh: (bb, hh, 0, 0)),
            pl.BlockSpec((1, dk), lambda bb, hh: (hh, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, t, dv), lambda bb, hh: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda bb, hh: (bb, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, h, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(r, k, v, log_w, u)
    return y, fin
