"""Pallas TPU kernel: tile-local density partial sort for budgeted
top-k selection.

Each grid step sorts one client tile's ``tile x M`` candidate block into
a single descending (density, flat-index) list — the tile-local partial
sort. The cross-tile merge happens *as the budget walk consumes the
per-tile lists* (``ops.budgeted_topk``): each greedy step takes the best
still-feasible head across tiles, which is exactly the global greedy
order because the pick order is a strict total order, so no second
merge pass over HBM is needed and selection is one kernel launch plus
the walk.

Layout contract (what Mosaic accepts): the wrapper computes the P2
density table with ``ref.pair_density`` — the oracle's own primitive
sequence, so the sort keys are bitwise the jnp path's on every backend
— and lays each tile's ``tile * M`` densities out row-major as one
``(R, 128)`` block, padded with -inf to ``P = R * 128`` entries (P a
power of two, R >= 8 so every block fills whole sublane tiles). The
block is the array's last two dims; the tile axis is a squeezed leading
block dim. Inside the kernel the flat candidate indices come from an
iota, and a bitonic network sorts the block entirely in VMEM: the
partner exchange at distance ``2^j`` is a lane roll (``2^j < 128``) or
a sublane roll (``2^j >= 128``) — no gathers, no lane-splitting
reshapes. Ties break toward the larger flat index, mirroring the legacy
reversed stable argsort. Padded entries carry density -inf / index -1
and sink to the tail.

CPU fallback semantics: ``use_kernel=False`` (the production CPU path)
sorts the whole density table with one argsort in ``ref.py`` — a single
segment — and feeds the same walk; ``interpret=True`` runs this body per
grid step under the Pallas interpreter for parity tests. All layouts
produce bitwise-identical assignments.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.budgeted_topk.ref import pair_density
from repro.kernels.common import resolve_interpret

LANES = 128
MIN_ROWS = 8            # one f32 sublane tile: the smallest block Mosaic pads
                        # nothing onto

Roll = Callable[[jax.Array, int, int], jax.Array]


def sort_width(span: int) -> Tuple[int, int]:
    """(P, R): the power-of-two sort width for ``span`` candidates and
    its row count in the (R, 128) block layout."""
    p = max(1 << (max(span, 1) - 1).bit_length(), LANES * MIN_ROWS)
    return p, p // LANES


def bitonic_sort_desc(d: jax.Array, ix: jax.Array, roll: Roll = jnp.roll
                      ) -> Tuple[jax.Array, jax.Array]:
    """Sort (R, L) key/index blocks by (key desc, index desc) in
    row-major position order; R and L powers of two. A roll-and-select
    network: partners at distance ``dist`` sit ``dist`` lanes away
    (``dist < L``) or ``dist / L`` rows away.

    ``roll`` is ``jnp.roll`` outside a kernel and ``pltpu.roll`` inside
    one; both move element ``i`` to ``i + shift`` (``chip_smoke.py``
    holds the compiled sort to the jnp one on the chip). The lower
    element of each pair takes its partner from the roll by
    ``size - shift``, the upper one from the roll by ``shift``."""
    rows, lanes = d.shape
    p = rows * lanes
    assert p & (p - 1) == 0 and lanes & (lanes - 1) == 0, (rows, lanes)
    pos = (jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) * lanes
           + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1))

    def partner(x, dist, lower):
        axis, size = (1, lanes) if dist < lanes else (0, rows)
        shift = dist if dist < lanes else dist // lanes
        return jnp.where(lower, roll(x, size - shift, axis),
                         roll(x, shift, axis))

    stages = p.bit_length() - 1
    for k in range(1, stages + 1):
        block = 1 << k
        desc = (pos & block) == 0
        for j in range(k - 1, -1, -1):
            dist = 1 << j
            lower = (pos & dist) == 0
            pd, pi = partner(d, dist, lower), partner(ix, dist, lower)
            a_d, b_d = jnp.where(lower, d, pd), jnp.where(lower, pd, d)
            a_i, b_i = jnp.where(lower, ix, pi), jnp.where(lower, pi, ix)
            a_first = (a_d > b_d) | ((a_d == b_d) & (a_i >= b_i))
            swap = (desc & ~a_first) | (~desc & a_first)
            d, ix = jnp.where(swap, pd, d), jnp.where(swap, pi, ix)
    return d, ix


def _kernel(d_ref, d_out, i_out, *, span):
    rows, lanes = d_ref.shape
    pos = (jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) * lanes
           + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1))
    ix = jnp.where(pos < span, pl.program_id(0) * span + pos, -1)
    d, ix = bitonic_sort_desc(d_ref[...], ix, roll=pltpu.roll)
    d_out[...] = d
    i_out[...] = ix


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def density_sort_kernel(values: jax.Array, costs: jax.Array,
                        eligible: jax.Array, tile: int = 128,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """values (N, M), costs (N,), eligible (N, M) bool ->
    (densities, flat_indices), each (num_tiles, P) with every row sorted
    (density desc, index desc); P from ``sort_width(tile * M)``.
    ``interpret=None`` resolves by backend (``resolve_interpret``)."""
    n, m = values.shape
    pad = (-n) % tile
    dens = pair_density(values, costs, eligible).astype(jnp.float32)
    nt = (n + pad) // tile
    span = tile * m
    p, rows = sort_width(span)
    dens = jnp.pad(dens, ((0, pad), (0, 0)), constant_values=-jnp.inf)
    dens = jnp.pad(dens.reshape(nt, span), ((0, 0), (0, p - span)),
                   constant_values=-jnp.inf).reshape(nt, rows, LANES)
    block = pl.BlockSpec((None, rows, LANES), lambda i: (i, 0, 0))
    d_s, i_s = pl.pallas_call(
        functools.partial(_kernel, span=span),
        grid=(nt,),
        in_specs=[block],
        out_specs=[block, block],
        out_shape=[jax.ShapeDtypeStruct((nt, rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((nt, rows, LANES), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(dens)
    return d_s.reshape(nt, p), i_s.reshape(nt, p)
