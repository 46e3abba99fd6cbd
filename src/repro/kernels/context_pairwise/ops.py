"""jit'd public wrapper: fused Eq. 4/5 pairwise context realization.

On TPU the Pallas kernel is used (interpret=False); this container is
CPU-only so ``use_kernel=True`` runs the same kernel body in interpret
mode while the default routes through the pure-jnp oracle. The caller
(``repro.sim.core.sim_round``) resolves its ``SimSpec.use_kernel`` knob
through ``repro.kernels.common.resolve_kernel_mode`` so all three paths
share the fleet-wide convention.

``best_tile`` is the client-axis tile autotuner, same pattern as
``masked_aggregate.ops.best_tile``: callers that do not pin a tile
(``SimSpec.kernel_tile == 0``) take its pick instead of a hardcoded 128.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.common import fastest
from repro.kernels.context_pairwise.kernel import context_pairwise_kernel
from repro.kernels.context_pairwise.ref import (PairwiseContext,
                                               pairwise_context_ref)

DEFAULT_TILE = 128


@functools.lru_cache(maxsize=None)
def best_tile(num_clients: int, num_es: int,
              candidates: Tuple[int, ...] = (64, 128, 256, 512)) -> int:
    """Pick the client-axis tile by timing candidates on the current
    backend. Only meaningful where the compiled kernel actually runs
    (TPU): elsewhere the jnp oracle is the fast path and interpret-mode
    timings say nothing about the lowered kernel, so the default tile is
    returned without timing. Cached per (N, M)."""
    if jax.default_backend() != "tpu":
        return DEFAULT_TILE
    n, m = max(int(num_clients), 1), max(int(num_es), 1)

    def inputs():
        key = jax.random.PRNGKey(0)
        fad = jnp.ones((n, m), jnp.float32)
        return (jax.random.uniform(key, (n, 2), jnp.float32, -1.5, 1.5),
                jax.random.uniform(key, (m, 2), jnp.float32, -1.5, 1.5),
                jnp.full((n,), 1e6, jnp.float32),
                jnp.full((n,), 1e9, jnp.float32), fad, fad)

    return fastest(candidates, inputs,
                   lambda tile, *a: context_pairwise_kernel(
                       *a, tx_w=0.2, noise_psd_w=3.98e-21, update_bits=1e5,
                       workload=1e7, tile=tile, interpret=False))


def pairwise_context(pos, es, bandwidth, compute, fad_dt, fad_ut, *,
                     tx_w, noise_psd_w, update_bits, workload,
                     use_kernel: bool = False, tile: int = 0,
                     interpret: Optional[bool] = None) -> PairwiseContext:
    """pos (N, 2), es (M, 2), bandwidth/compute (N,), fad_dt/fad_ut
    (N, M) -> ``PairwiseContext`` of four (N, M) float32 tensors.

    ``tile=0`` consults the ``best_tile`` autotuner."""
    if use_kernel:
        t = int(tile) or best_tile(int(fad_dt.shape[0]),
                                   int(fad_dt.shape[1]))
        return context_pairwise_kernel(
            pos, es, bandwidth, compute, fad_dt, fad_ut, tx_w=tx_w,
            noise_psd_w=noise_psd_w, update_bits=update_bits,
            workload=workload, tile=t, interpret=interpret)
    return pairwise_context_ref(
        pos, es, bandwidth, compute, fad_dt, fad_ut, tx_w=tx_w,
        noise_psd_w=noise_psd_w, update_bits=update_bits, workload=workload)
