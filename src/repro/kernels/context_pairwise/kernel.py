"""Pallas TPU kernel: fused Eq. 4/5 pairwise context realization.

One tiled pass over the (N, M) client x edge-server grid computes
distance -> path-loss gain -> Eq. 4 Shannon rates (per fading draw and
at the fading mean) -> Eq. 5 download/compute/upload latency without
materializing any of the intermediate (N, M) tensors in HBM: the
per-link fading x gain products, the three SNR tables, the two
directional rates and the three latency terms all live and die inside
one VMEM block, and only the four consumed outputs (distance, gain,
mean rate, latency) are written back.

VMEM tiling contract: the grid is one program per client tile (``tile``
rows, N padded up to a multiple); the ES axis M rides whole inside every
block (M is at most tens), as do the (1, M) ES coordinate rows. The
per-block VMEM footprint is O(tile x M) floats. The physics scalars
(tx power, noise PSD, update bits, workload) are static Python floats
baked in at trace time — ``SimSpec`` is hashable/static, so each network
spec compiles its own specialized kernel.

CPU fallback semantics: ``interpret=True`` runs this same body per grid
step under the Pallas interpreter — the debugging/parity path, not a
fast path; production CPU callers take the jnp oracle via
``ops.pairwise_context(use_kernel=False)``. The body calls the *same*
``ref.py`` rate/latency helpers on its VMEM tiles, so kernel and oracle
share one float32 primitive sequence and agree bitwise.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.network import path_loss_gain
from repro.kernels.common import resolve_interpret
from repro.kernels.context_pairwise.ref import (PairwiseContext, latency,
                                               shannon_rate)


def _kernel(pos_ref, es_ref, bw_ref, comp_ref, fdt_ref, fut_ref,
            d_ref, g_ref, r_ref, t_ref, *, tx_w, noise_psd_w, update_bits,
            workload):
    pos = pos_ref[...]                            # (T, 2)
    es = es_ref[...]                              # (M, 2)
    # the exact primitive sequence of the ref/oracle distance line — any
    # algebraically-equal variant costs bitwise kernel-on/off parity
    d = jnp.sqrt(jnp.sum((pos[:, None] - es[None]) ** 2, -1))
    g0 = path_loss_gain(d, xp=jnp)
    bw = bw_ref[...]                              # (T, 1)
    tau = latency(bw, comp_ref[...], fdt_ref[...], fut_ref[...], g0,
                  tx_w=tx_w, noise_psd_w=noise_psd_w,
                  update_bits=update_bits, workload=workload)
    rate = shannon_rate(bw, 1.0, g0, tx_w=tx_w, noise_psd_w=noise_psd_w)
    d_ref[...] = d
    g_ref[...] = g0
    r_ref[...] = rate
    t_ref[...] = tau


@functools.partial(jax.jit, static_argnames=(
    "tx_w", "noise_psd_w", "update_bits", "workload", "tile", "interpret"))
def context_pairwise_kernel(pos, es, bandwidth, compute, fad_dt, fad_ut, *,
                            tx_w, noise_psd_w, update_bits, workload,
                            tile: int = 128, interpret: Optional[bool] = None
                            ) -> PairwiseContext:
    """Same signature/semantics as ``pairwise_context_ref`` (modulo the
    static tile/interpret knobs)."""
    n, m = fad_dt.shape
    pad = (-n) % tile
    if pad:
        pos = jnp.pad(pos, ((0, pad), (0, 0)))
        # pad resources with 1.0 so padded rows stay finite (sliced off)
        bandwidth = jnp.pad(bandwidth, (0, pad), constant_values=1.0)
        compute = jnp.pad(compute, (0, pad), constant_values=1.0)
        fad_dt = jnp.pad(fad_dt, ((0, pad), (0, 0)), constant_values=1.0)
        fad_ut = jnp.pad(fad_ut, ((0, pad), (0, 0)), constant_values=1.0)
    np_ = pos.shape[0]
    f32 = jnp.float32
    kern = functools.partial(_kernel, tx_w=tx_w, noise_psd_w=noise_psd_w,
                             update_bits=update_bits, workload=workload)
    tile_nm = pl.BlockSpec((tile, m), lambda i: (i, 0))
    outs = pl.pallas_call(
        kern,
        grid=(np_ // tile,),
        in_specs=[
            pl.BlockSpec((tile, 2), lambda i: (i, 0)),     # positions
            pl.BlockSpec((m, 2), lambda i: (0, 0)),        # ES coordinates
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),     # bandwidth col
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),     # compute col
            tile_nm,                                       # download fading
            tile_nm,                                       # upload fading
        ],
        out_specs=[tile_nm, tile_nm, tile_nm, tile_nm],
        out_shape=[jax.ShapeDtypeStruct((np_, m), f32)] * 4,
        interpret=resolve_interpret(interpret),
    )(pos.astype(f32), es.astype(f32),
      bandwidth.reshape(np_, 1).astype(f32),
      compute.reshape(np_, 1).astype(f32),
      fad_dt.astype(f32), fad_ut.astype(f32))
    return PairwiseContext(*(o[:n] for o in outs))
