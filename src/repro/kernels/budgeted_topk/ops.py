"""jit'd public wrappers: budgeted top-k selection over sorted candidates.

``budgeted_topk`` solves P2 (density greedy) and ``flgreedy_topk`` P3
(sqrt-utility cost-benefit greedy) over a *sorted, flattened* candidate
layout instead of the legacy (N, M)-wide argmax loop: the density table
is computed and tile-sorted once (Pallas kernel on TPU, one jnp argsort
on CPU — ``use_kernel`` routing as in ``fed.batched``), and the budget
walk then takes one greedy pick per iteration by scanning each segment
for its first still-feasible head and merging the heads across segments.
Because the pick order is a strict total order (density desc, flat index
desc), per-tile segments merge to exactly the global greedy sequence —
the cross-tile merge under the budget constraint — and both layouts are
bitwise-identical to ``policies.solvers.greedy_assign``.

P3's marginal gains depend on the running utility total, so its pick
order cannot be pre-sorted (lazy evaluation is exact only because it
re-checks the heap top); ``flgreedy_topk`` therefore keeps the exact
iterative walk but runs it over the same compressed sorted layout,
recomputing gains per iteration — bitwise-identical to
``flgreedy_assign``.

``best_tile`` is the client-axis tile autotuner (TPU-only timing, the
``masked_aggregate.ops.best_tile`` pattern).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.budgeted_topk.kernel import density_sort_kernel
from repro.kernels.budgeted_topk.ref import (pair_density,
                                             sorted_candidates_ref)
from repro.kernels.common import fastest
from repro.obs import scopes

DEFAULT_TILE = 128

# A merge callback reduces candidate heads to one pick per iteration:
# (head_density, head_flat, aux_tuple) -> (ok, pick_flat, merged_aux).
# ``merge_heads`` below is the single-device reduction; the sharded
# cohort engine (repro.mesh.select) substitutes an ``all_gather``-based
# two-level reduction over the ("clients",) mesh axis. Because max is
# exactly associative and flat indices are globally unique, any merge
# topology yields the same pick sequence bitwise.
MergeFn = Callable[[jax.Array, jax.Array, Tuple[jax.Array, ...]],
                   Tuple[jax.Array, jax.Array, Tuple[jax.Array, ...]]]


@functools.lru_cache(maxsize=None)
def best_tile(num_clients: int, num_es: int,
              candidates: Tuple[int, ...] = (64, 128, 256)) -> int:
    """Time candidate client-axis tiles on TPU; default elsewhere (the
    jnp oracle is the CPU fast path and interpret timings say nothing
    about the lowered kernel). Cached per (N, M)."""
    if jax.default_backend() != "tpu":
        return DEFAULT_TILE
    n, m = max(int(num_clients), 1), max(int(num_es), 1)

    def inputs():
        return (jax.random.uniform(jax.random.PRNGKey(0), (n, m),
                                   jnp.float32),
                jnp.full((n,), 0.5, jnp.float32),
                jnp.ones((n, m), bool))

    return fastest(candidates, inputs,
                   lambda tile, *a: density_sort_kernel(
                       *a, tile=tile, interpret=False))


def sorted_candidates(values: jax.Array, costs: jax.Array,
                      eligible: jax.Array, use_kernel: bool = False,
                      tile: int = 0, interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """(density, flat_index) segments, each row sorted (density desc,
    index desc): (num_tiles, P) from the Pallas kernel, or one (1, N*M)
    segment from the jnp oracle. Padding rides as density -inf."""
    if use_kernel:
        t = int(tile) or best_tile(int(values.shape[0]),
                                   int(values.shape[1]))
        return density_sort_kernel(values, costs, eligible, tile=t,
                                   interpret=interpret)
    return sorted_candidates_ref(values, costs, eligible)


class Segments(NamedTuple):
    """Sorted candidate segments with globally-addressed columns.

    Rows are independent sorted segments (density desc, flat desc;
    padding as density -inf). ``flat`` carries *global* flat indices
    (``(client + base) * M + es``) so shards of a partitioned client
    axis can merge heads without renumbering; ``loc`` stays shard-local
    so the walk can index a shard-local assignment vector. ``cost`` and
    ``value`` are carried per column so the walk never indexes the dense
    ``(N,)``/``(N, M)`` tables — the property that lets a shard-local
    walk update the replicated budget vector after a remote pick."""
    density: jax.Array   # (nseg, P) selection density; pads -inf
    flat: jax.Array      # (nseg, P) global flat candidate index
    loc: jax.Array       # (nseg, P) local client row of the candidate
    es: jax.Array        # (nseg, P) ES column of the candidate
    cost: jax.Array      # (nseg, P) costs[loc]
    value: jax.Array     # (nseg, P) values[loc, es]


def build_segments(values: jax.Array, costs: jax.Array, eligible: jax.Array,
                   base=0, use_kernel: bool = False, tile: int = 0,
                   interpret: Optional[bool] = None) -> Segments:
    """Sorted candidate ``Segments`` over a (possibly shard-local)
    ``(n, M)`` block whose rows are global clients ``base .. base+n``.
    ``base`` may be traced (``axis_index * n_local`` under shard_map)."""
    n, m = values.shape
    d_s, i_s = sorted_candidates(values, costs, eligible,
                                 use_kernel=use_kernel, tile=tile,
                                 interpret=interpret)
    flat_l = jnp.clip(i_s, 0, n * m - 1)          # pads clip; d=-inf anyway
    loc, es = flat_l // m, flat_l % m
    return Segments(density=d_s,
                    flat=flat_l + jnp.asarray(base, flat_l.dtype) * m,
                    loc=loc, es=es, cost=costs[loc],
                    value=values.reshape(-1)[flat_l])


def identity_segments(values: jax.Array, costs: jax.Array,
                      eligible: jax.Array, base=0) -> Segments:
    """Unsorted single-segment candidate layout — no ``lax.sort``.

    Same column streams as ``build_segments`` in flat-index order
    instead of density order. The budget walks stay exact: P3 rescans
    every column per iteration anyway, and P2 consumes this layout with
    ``sorted_rows=False`` (masked max instead of first-feasible scan),
    which picks the identical head. This is the layout the sharded
    cohort engine uses *inside* ``shard_map``: with ``check_vma=False``
    the SPMD partitioner loses the manual-sharding annotation on
    ``lax.sort`` and re-partitions it as a global sharded sort —
    inserting cross-shard all-reduces that sum per-shard tables into
    garbage (observed on multi-device CPU whenever the ``"seed"`` mesh
    axis is split; see ``repro.mesh.select``)."""
    n, m = values.shape
    flat_l = jnp.arange(n * m, dtype=jnp.int32)
    loc, es = flat_l // m, flat_l % m
    one = lambda a: a.reshape(1, n * m)
    return Segments(density=one(pair_density(values, costs, eligible)),
                    flat=one(flat_l + jnp.asarray(base, jnp.int32) * m),
                    loc=one(loc), es=one(es),
                    cost=one(costs[loc]),
                    value=one(values.reshape(-1)))


def merge_heads(head_d, head_i, aux=()):
    """Single-device merge: max density, ties toward the larger flat
    index — the legacy argmax direction. Aux streams are resolved by the
    picked flat index; duplicate flats (clipped pads) share their aux
    values, so the lookup is unambiguous. Returns (ok, pick, aux)."""
    dmax = jnp.max(head_d)
    ok = dmax > -jnp.inf
    pick = jnp.maximum(jnp.max(jnp.where(head_d == dmax, head_i, -1)), 0)
    out = tuple(jnp.max(jnp.where(head_i == pick, a, -jnp.inf)) for a in aux)
    return ok, pick, out


def greedy_walk(segs: Segments, budgets: jax.Array, *, num_es: int,
                num_clients: int, local_clients: int = 0, base=0,
                merge: MergeFn = merge_heads, sync=None,
                sorted_rows: bool = True,
                dtype=jnp.float32) -> Tuple[jax.Array, jax.Array]:
    """The P2 density-greedy budget walk over sorted ``Segments``.

    One pick per iteration: each segment row exposes its first
    still-feasible head, ``merge`` reduces the heads to the global pick,
    and the budget/assignment state advances. With the default merge
    this is exactly ``budgeted_topk``'s legacy walk; a cross-shard merge
    runs the same walk with each shard holding only its own segments and
    a ``local_clients``-sized assignment slice (rows ``base ..
    base+local_clients`` of the global assignment). Returns
    ``(assign_local, remaining)``.

    ``sync`` (optional) maps the per-iteration live flag to the value
    the loop continues on. On a mesh whose collectives ride shared
    channels, every device must execute the body the same number of
    times even when its own walk finished earlier — pass an OR-reduction
    over the *other* mesh axes (``mesh.select`` does) so trip counts
    are mesh-uniform. Extra iterations are no-ops: a dead walk has no
    feasible candidate, so ``ok`` stays False and no state changes.

    ``sorted_rows=False`` consumes ``identity_segments``: the head of a
    row is found by a masked max over its feasible columns (density
    max, ties toward the larger flat index) instead of the first-
    feasible scan. Both select the exact candidate the sort order puts
    first, so the pick sequence is bitwise the same.
    """
    m = num_es
    n_loc = local_clients or num_clients
    seg = jnp.arange(segs.density.shape[0])
    base = jnp.asarray(base, jnp.int32)

    def cond(carry):
        assign, remaining, k, live = carry
        return live & (k < num_clients)

    def body(carry):
        assign, remaining, k, live = carry
        feas = ((segs.density > 0.0) & (assign[segs.loc] < 0)
                & (segs.cost <= remaining[segs.es] + 1e-12))
        if sorted_rows:
            hit = feas.any(axis=1)
            first = jnp.argmax(feas, axis=1)      # first feasible = best:
            head_d = jnp.where(hit, segs.density[seg, first], -jnp.inf)
            head_i = jnp.where(hit, segs.flat[seg, first], -1)  # rows sorted
            head_c = jnp.where(hit, segs.cost[seg, first], -jnp.inf)
        else:
            dm = jnp.where(feas, segs.density, -jnp.inf)
            head_d = jnp.max(dm, axis=1)
            hit = head_d > -jnp.inf
            head_i = jnp.where(hit, jnp.max(jnp.where(
                dm == head_d[:, None], segs.flat, -1), axis=1), -1)
            head_c = jnp.max(jnp.where(segs.flat == head_i[:, None],
                                       segs.cost, -jnp.inf), axis=1)
        ok, pick, (cost,) = merge(head_d, head_i, (head_c,))
        gi, j = pick // m, pick % m
        owns = ok & (gi >= base) & (gi < base + n_loc)
        iloc = jnp.clip(gi - base, 0, n_loc - 1)
        assign = jnp.where(owns,
                           assign.at[iloc].set(j.astype(assign.dtype)),
                           assign)
        remaining = jnp.where(ok, remaining.at[j].add(-cost), remaining)
        live = ok if sync is None else sync(ok)
        return assign, remaining, k + 1, live

    carry = (jnp.full(n_loc, -1, jnp.int32), budgets.astype(dtype),
             jnp.zeros((), jnp.int32), jnp.ones((), bool))
    with jax.named_scope(scopes.WALK):
        assign, remaining, _, _ = lax.while_loop(cond, body, carry)
    return assign, remaining


def flgreedy_walk(segs: Segments, budgets: jax.Array, *, num_es: int,
                  num_clients: int, m_div: float, local_clients: int = 0,
                  base=0, merge: MergeFn = merge_heads, sync=None,
                  dtype=jnp.float32) -> Tuple[jax.Array, jax.Array]:
    """The P3 cost-benefit (Eq. 19 sqrt utility) walk over ``Segments``.

    Marginal gains depend on the running utility total, so the pick
    order cannot be pre-sorted; the walk recomputes gains per iteration
    over the flattened candidate columns and ``merge`` reduces the full
    gain-rate stream (heads are the whole columns here — exactness needs
    every candidate rescored, not just segment heads). Same shard and
    ``sync`` contract as ``greedy_walk``."""
    m = num_es
    n_loc = local_clients or num_clients
    base = jnp.asarray(base, jnp.int32)
    flat_r = segs.flat.ravel()
    loc_r, es_r = segs.loc.ravel(), segs.es.ravel()
    v_r, c_r = segs.value.ravel(), segs.cost.ravel()
    cand_r = segs.density.ravel() > -jnp.inf     # eligible, unpadded

    def util(total):
        return jnp.sqrt(jnp.maximum(total, 0.0) / m_div)

    def cond(carry):
        assign, remaining, total, k, live = carry
        return live & (k < num_clients)

    def body(carry):
        assign, remaining, total, k, live = carry
        gains = util(total + v_r) - util(total)
        feas = (cand_r & (c_r > 0) & (assign[loc_r] < 0)
                & (c_r <= remaining[es_r] + 1e-12))
        r = jnp.where(feas, gains / jnp.maximum(c_r, 1e-12), -jnp.inf)
        ok0, pick, (g, v, c) = merge(r, flat_r, (gains, v_r, c_r))
        ok = ok0 & (g > 1e-15)
        gi, j = pick // m, pick % m
        owns = ok & (gi >= base) & (gi < base + n_loc)
        iloc = jnp.clip(gi - base, 0, n_loc - 1)
        assign = jnp.where(owns,
                           assign.at[iloc].set(j.astype(assign.dtype)),
                           assign)
        remaining = jnp.where(ok, remaining.at[j].add(-c), remaining)
        total = jnp.where(ok, total + v, total)
        live = ok if sync is None else sync(ok)
        return assign, remaining, total, k + 1, live

    carry = (jnp.full(n_loc, -1, jnp.int32), budgets.astype(dtype),
             jnp.zeros((), dtype), jnp.zeros((), jnp.int32),
             jnp.ones((), bool))
    with jax.named_scope(scopes.WALK):
        assign, remaining, _, _, _ = lax.while_loop(cond, body, carry)
    return assign, remaining


@functools.partial(jax.jit, static_argnames=("use_kernel", "tile",
                                             "interpret"))
def budgeted_topk(values: jax.Array, costs: jax.Array, budgets: jax.Array,
                  eligible: jax.Array, use_kernel: bool = False,
                  tile: int = 0, interpret: Optional[bool] = None
                  ) -> jax.Array:
    """Density greedy for P2 over sorted candidates. values (N, M),
    costs (N,), budgets (M,), eligible (N, M) bool -> assign (N,) int32
    (-1 = unselected); bitwise-identical to ``greedy_assign``."""
    n, m = values.shape
    segs = build_segments(values, costs, eligible, use_kernel=use_kernel,
                          tile=tile, interpret=interpret)
    assign, _ = greedy_walk(segs, budgets, num_es=m, num_clients=n,
                            dtype=values.dtype)
    return assign


@functools.partial(jax.jit, static_argnames=("num_es", "use_kernel",
                                             "tile", "interpret"))
def flgreedy_topk(values: jax.Array, costs: jax.Array, budgets: jax.Array,
                  eligible: jax.Array, num_es: int = 0,
                  use_kernel: bool = False, tile: int = 0,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Cost-benefit greedy for P3 (Eq. 19 sqrt utility) over the same
    compressed sorted layout; bitwise-identical to ``flgreedy_assign``."""
    n, m = values.shape
    segs = build_segments(values, costs, eligible, use_kernel=use_kernel,
                          tile=tile, interpret=interpret)
    assign, _ = flgreedy_walk(segs, budgets, num_es=m, num_clients=n,
                              m_div=float(num_es or m), dtype=values.dtype)
    return assign
