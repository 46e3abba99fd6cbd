"""Pallas TPU kernel: fused MoE router — softmax + top-k gate extraction.

Per token-tile the kernel computes router probabilities over E experts in
VMEM and extracts the top-k (gate, index) pairs with k rounds of
masked argmax (k <= 8 << E, so iterative max beats a full sort on the VPU
and never materializes the (T, E) sorted tensor in HBM). Gates are
renormalized to sum to 1 (the combine convention used by moe_block).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret

NEG_INF = -1e30


def _kernel(logits_ref, gates_ref, idx_ref, *, top_k: int):
    x = logits_ref[...].astype(jnp.float32)              # (T_tile, E)
    # stable softmax over experts
    m = jnp.max(x, axis=-1, keepdims=True)
    p = jnp.exp(x - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    total = jnp.zeros((x.shape[0], 1), jnp.float32)
    work = p
    for j in range(top_k):
        best = jnp.max(work, axis=-1, keepdims=True)     # (T, 1)
        arg = jnp.argmax(work, axis=-1)                  # (T,)
        gates_ref[:, j] = best[:, 0]
        idx_ref[:, j] = arg.astype(jnp.int32)
        total = total + best
        # mask out the chosen expert for the next round
        onehot = jax.nn.one_hot(arg, x.shape[1], dtype=jnp.float32)
        work = work - onehot * work
    # renormalize the k gates
    for j in range(top_k):
        gates_ref[:, j] = gates_ref[:, j] / jnp.maximum(total[:, 0], 1e-30)


@functools.partial(jax.jit, static_argnames=("top_k", "tile", "interpret"))
def moe_router_kernel(logits: jax.Array, top_k: int, tile: int = 256,
                      interpret: Optional[bool] = None):
    """logits: (T, E) fp32/bf16. Returns (gates (T,k) f32, idx (T,k) i32)."""
    t, e = logits.shape
    tile = min(tile, t)
    pad = (-t) % tile
    if pad:
        logits = jnp.pad(logits, ((0, pad), (0, 0)),
                         constant_values=NEG_INF)
    tp = logits.shape[0]
    kernel = functools.partial(_kernel, top_k=top_k)
    gates, idx = pl.pallas_call(
        kernel,
        grid=(tp // tile,),
        in_specs=[pl.BlockSpec((tile, e), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((tile, top_k), lambda i: (i, 0)),
            pl.BlockSpec((tile, top_k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, top_k), jnp.float32),
            jax.ShapeDtypeStruct((tp, top_k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(logits)
    return gates[:t], idx[:t]
