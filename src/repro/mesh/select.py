"""Hierarchical budgeted selection over a client-sharded candidate table.

The dense P2/P3 solvers walk one sorted candidate layout per round
(``kernels.budgeted_topk``). On a client-sharded mesh each shard can
only sort *its* rows, so selection becomes two-level: every shard scans
its own sorted segments for the first still-feasible head (the existing
tile argument — rows are sorted, so the first feasible entry is the
segment's best), and an ``all_gather`` of the per-shard champion scalars
merges the heads into the global pick. Because max is exactly
associative and flat candidate indices are globally unique, the merge
topology is invisible: the pick sequence — and therefore the assignment
— is bitwise identical to ``greedy_assign``/``flgreedy_assign``
(property-tested in ``tests/test_mesh_select.py``).

Two entry points share the walk in ``kernels.budgeted_topk.ops``:

* ``shard_assign`` — the distributed form, called per shard inside
  ``shard_map`` (``repro.mesh.engine``) with shard-local (n_local, M)
  tables and the ``("clients",)`` axis name;
* ``hier_greedy_assign``/``hier_flgreedy_assign`` — the single-device
  emulation: per-shard segments stacked into one walk with the default
  merge. Arithmetically the same reduction tree, so it pins the
  distributed path's bitwise contract at any shard count without
  needing a multi-device runtime.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.budgeted_topk.ops import (Segments, build_segments,
                                             flgreedy_walk, greedy_walk,
                                             identity_segments)


def merge_over_shards(axis_name: str):
    """Cross-shard head merge: reduce locally to one champion (density,
    flat, aux...) scalar set, ``all_gather`` the champions over
    ``axis_name``, reduce again. Ties break toward the larger *global*
    flat index at both levels, so the two-level reduction equals the
    dense single-level merge exactly (max is associative; shards own
    disjoint flat ranges, so champion lookups never collide)."""

    def merge(head_d, head_i, aux=()):
        ld = jnp.max(head_d)
        li = jnp.max(jnp.where(head_d == ld, head_i, -1))
        laux = tuple(jnp.max(jnp.where(head_i == li, a, -jnp.inf))
                     for a in aux)
        gd = lax.all_gather(ld, axis_name)
        gi = lax.all_gather(li, axis_name)
        gaux = tuple(lax.all_gather(a, axis_name) for a in laux)
        dmax = jnp.max(gd)
        ok = dmax > -jnp.inf
        pick = jnp.max(jnp.where(gd == dmax, gi, -1))
        out = tuple(jnp.max(jnp.where(gi == pick, a, -jnp.inf))
                    for a in gaux)
        return ok, jnp.maximum(pick, 0), out

    return merge


def shard_assign(values: jax.Array, costs: jax.Array, eligible: jax.Array,
                 budgets: jax.Array, *, axis_name: str, num_clients: int,
                 sqrt_utility: bool = False, num_es_div: int = 0,
                 sync_axes: tuple = (), use_kernel: bool = False,
                 tile: int = 0, interpret: Optional[bool] = None
                 ) -> jax.Array:
    """One shard's half of the hierarchical selection (inside shard_map).

    values/eligible (n_local, M), costs (n_local,): this shard's rows of
    the dense tables; budgets (M,) replicated. Returns the shard's
    (n_local,) rows of the global assignment — bitwise the dense
    solver's rows for the full (num_clients, M) table.

    ``sync_axes`` names the *other* mesh axes the walk must stay in
    lockstep with (e.g. ``("seed",)`` in the cohort engine): the walk's
    collectives ride shared channels, so every device on the mesh has
    to execute the loop body the same number of times — the live flag
    is OR-reduced over these axes, and finished rows spin through
    no-op iterations until the whole mesh is done.

    The jnp path deliberately avoids ``lax.sort``: inside a
    ``check_vma=False`` shard_map body the SPMD partitioner drops the
    sort's manual-sharding annotation and re-partitions it as a global
    sharded sort, inserting cross-shard all-reduces that *sum* the
    per-shard tables into garbage (reproduced on multi-device CPU
    whenever a second mesh axis is split). ``identity_segments`` + the
    ``sorted_rows=False`` head scan pick the identical candidate
    sequence with only elementwise/reduce/gather ops, which partition
    correctly. The Pallas kernel path keeps its tile sort — a
    ``pallas_call`` is opaque to the partitioner.
    """
    n_local, m = values.shape
    base = lax.axis_index(axis_name) * n_local
    if use_kernel:
        segs = build_segments(values, costs, eligible, base=base,
                              use_kernel=True, tile=tile,
                              interpret=interpret)
        sorted_rows = True
    else:
        segs = identity_segments(values, costs, eligible, base=base)
        sorted_rows = False
    merge = merge_over_shards(axis_name)
    sync = None
    if sync_axes:
        def sync(live):
            return lax.pmax(live.astype(jnp.int32), sync_axes) > 0
    if sqrt_utility:
        assign, _ = flgreedy_walk(segs, budgets, num_es=m,
                                  num_clients=num_clients,
                                  m_div=float(num_es_div or m),
                                  local_clients=n_local, base=base,
                                  merge=merge, sync=sync,
                                  dtype=values.dtype)
    else:
        assign, _ = greedy_walk(segs, budgets, num_es=m,
                                num_clients=num_clients,
                                local_clients=n_local, base=base,
                                merge=merge, sync=sync,
                                sorted_rows=sorted_rows,
                                dtype=values.dtype)
    return assign


# -- single-device emulation -------------------------------------------------


def shard_segments(values: jax.Array, costs: jax.Array, eligible: jax.Array,
                   num_shards: int, use_kernel: bool = False, tile: int = 0,
                   interpret: Optional[bool] = None) -> Segments:
    """Per-shard sorted segments of a dense (N, M) table, stacked: what
    ``num_shards`` mesh shards would each build locally, with globally
    addressed flat indices and global ``loc`` rows (the emulation walks
    one global assignment vector). N must divide by ``num_shards``."""
    n, m = values.shape
    n_local = n // num_shards
    build = functools.partial(build_segments, use_kernel=use_kernel,
                              tile=tile, interpret=interpret)
    segs = jax.vmap(build)(
        values.reshape(num_shards, n_local, m),
        costs.reshape(num_shards, n_local),
        eligible.reshape(num_shards, n_local, m),
        jnp.arange(num_shards, dtype=jnp.int32) * n_local)
    flat = Segments(*(a.reshape((-1,) + a.shape[2:]) for a in segs))
    return flat._replace(loc=flat.flat // m)


def _pad_clients(values, costs, eligible, num_shards: int):
    n = values.shape[0]
    n_pad = -(-n // num_shards) * num_shards
    if n_pad == n:
        return values, costs, eligible, n
    pad = n_pad - n
    # padded rows are ineligible -> density -inf -> never picked
    return (jnp.pad(values, ((0, pad), (0, 0))),
            jnp.pad(costs, (0, pad), constant_values=1.0),
            jnp.pad(eligible, ((0, pad), (0, 0))), n)


@functools.partial(jax.jit, static_argnames=("num_shards", "use_kernel",
                                             "tile", "interpret"))
def hier_greedy_assign(values: jax.Array, costs: jax.Array,
                       budgets: jax.Array, eligible: jax.Array,
                       num_shards: int = 1, use_kernel: bool = False,
                       tile: int = 0, interpret: Optional[bool] = None
                  ) -> jax.Array:
    """P2 density greedy over ``num_shards`` per-shard segment sets —
    bitwise ``greedy_assign`` at any shard count. N that does not divide
    evenly is padded with ineligible rows (a real mesh pads the same
    way); the pad rows are sliced off the returned (N,) assignment."""
    values, costs, eligible, n = _pad_clients(values, costs, eligible,
                                              num_shards)
    segs = shard_segments(values, costs, eligible, num_shards,
                          use_kernel=use_kernel, tile=tile,
                          interpret=interpret)
    assign, _ = greedy_walk(segs, budgets, num_es=values.shape[1],
                            num_clients=values.shape[0],
                            dtype=values.dtype)
    return assign[:n]


@functools.partial(jax.jit, static_argnames=("num_shards", "num_es",
                                             "use_kernel", "tile",
                                             "interpret"))
def hier_flgreedy_assign(values: jax.Array, costs: jax.Array,
                         budgets: jax.Array, eligible: jax.Array,
                         num_shards: int = 1, num_es: int = 0,
                         use_kernel: bool = False, tile: int = 0,
                         interpret: Optional[bool] = None) -> jax.Array:
    """P3 sqrt-utility cost-benefit greedy over per-shard segments —
    bitwise ``flgreedy_assign`` at any shard count."""
    m = values.shape[1]
    values, costs, eligible, n = _pad_clients(values, costs, eligible,
                                              num_shards)
    segs = shard_segments(values, costs, eligible, num_shards,
                          use_kernel=use_kernel, tile=tile,
                          interpret=interpret)
    assign, _ = flgreedy_walk(segs, budgets, num_es=m,
                              num_clients=values.shape[0],
                              m_div=float(num_es or m),
                              dtype=values.dtype)
    return assign[:n]
