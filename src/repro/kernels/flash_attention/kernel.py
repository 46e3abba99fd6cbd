"""Pallas TPU kernel: causal GQA flash attention (forward) with optional
sliding window.

Grid (B, H, S/BQ, S/BK); the innermost KV axis iterates sequentially on TPU,
so the online-softmax running statistics (m, l) and the output accumulator
live in VMEM scratch that persists across KV steps. Blocks:
  q:   (BQ, D) for query tile iq
  k,v: (BK, D) for kv tile ik of the matching GQA kv head (h * KV // H)
Fully-masked (future / out-of-window) KV tiles are skipped with pl.when —
this is what makes causal attention ~2x and sliding-window attention
O(S * W) instead of O(S^2).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            sm_scale: float, block_q: int, block_k: int, causal: bool,
            window: int, seq_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    # tile-level skip decisions (evaluated per grid step via pl.when)
    live = jnp.asarray(True)
    if causal:
        live &= k_start <= q_start + block_q - 1            # causal reachable
    if window > 0:
        live &= k_start + block_k - 1 > q_start - window    # window reachable

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)                 # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (BQ, BK)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = kpos < seq_len
        if causal:
            ok &= kpos <= qpos
        if window > 0:
            ok &= kpos > qpos - window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]                                 # (BQ, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(ok, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "sm_scale", "block_q",
                              "block_k", "interpret"))
def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True, window: int = 0,
                           sm_scale: float = 0.0, block_q: int = 128,
                           block_k: int = 128,
                           interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, H, S, D); k, v: (B, KV, S, D) with H % KV == 0. Returns like q."""
    b, h, s, d = q.shape
    kv = k.shape[1]
    assert h % kv == 0, (h, kv)
    if sm_scale == 0.0:
        sm_scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    pad = (-s) % block_q
    padk = (-s) % block_k
    if pad or padk:
        p = max(pad, padk)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, p), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, p), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, p), (0, 0)))
    sp = q.shape[2]
    grid = (b, h, sp // block_q, sp // block_k)
    kernel = functools.partial(
        _kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, seq_len=s)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, qq, kk, kv_=kv, h_=h:
                         (bb, (hh * kv_) // h_, kk, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, qq, kk, kv_=kv, h_=h:
                         (bb, (hh * kv_) // h_, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return out[:, :, :s]
