"""Vectorized JAX solvers for the per-round selection problem (P2/P3).

``greedy_assign`` is a fixed-iteration (N steps) re-implementation of the
legacy ``repro.core.selection.greedy_select`` Python argsort loop. Because
budget feasibility is monotone non-increasing as the greedy proceeds,
"walk the density-sorted list, skipping infeasible pairs" is equivalent to
"repeatedly take the highest-density currently-feasible pair" — which is
what the fori_loop below does, making one round's solve a single jittable
program with static shapes. Ties are broken toward the larger flat index
to mirror the legacy reversed stable argsort.

``flgreedy_assign`` is the non-lazy exact variant of the FLGreedy
cost-benefit greedy for the sqrt (submodular) utility: lazy evaluation in
the legacy heap solver is an exact speedup, so recomputing all marginal
gains each iteration selects the same pairs (up to ties).

``random_assign`` draws a feasible random assignment (uniform over
feasible ESs per client in a random client order) with jax.random.

Both greedy solvers accept ``use_kernel``/``tile``/``interpret`` knobs
(the fleet-wide Pallas routing convention, ``repro.kernels.common``):
``use_kernel=None`` keeps this while-loop body on CPU and routes to the
``repro.kernels.budgeted_topk`` sorted-candidate walk — tile-local
density sort in one kernel launch, budget walk over the per-tile heads —
on TPU. All paths are bitwise-identical (the pick order is a strict
total order), property-tested in ``tests/test_budgeted_topk.py``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.budgeted_topk.ops import budgeted_topk, flgreedy_topk
from repro.kernels.common import resolve_kernel_mode
from repro.obs import scopes


def feasible_cohort_bound(budget: float, min_cost: float,
                          num_clients: int) -> int:
    """Largest per-ES cohort any budget-feasible assignment can produce.

    Every solver here (and every legacy policy) only adds a client to an
    ES while ``cost <= remaining budget``, so a cohort can never exceed
    ``floor(B / min cost)``. This bound is what lets the fused experiment
    engine pin a static slot capacity (``repro.experiment.packing``)
    without seeing the assignments first.
    """
    if min_cost <= 0.0:
        return int(num_clients)
    return int(min(num_clients,
                   max(1, math.floor(budget / min_cost + 1e-9))))


@partial(jax.jit, static_argnames=("use_kernel", "tile", "interpret"))
def greedy_assign(values: jax.Array, costs: jax.Array, budgets: jax.Array,
                  eligible: jax.Array,
                  use_kernel: Optional[bool] = None, tile: int = 0,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Density greedy for P2. values (N,M), costs (N,), budgets (M,),
    eligible (N,M) bool -> assign (N,) int32 (-1 = unselected)."""
    use_k, _ = resolve_kernel_mode(use_kernel)
    if use_k:
        return budgeted_topk(values, costs, budgets, eligible,
                             use_kernel=True, tile=tile,
                             interpret=interpret)
    n, m = values.shape
    density = jnp.where(eligible,
                        values / jnp.maximum(costs[:, None], 1e-12),
                        -jnp.inf)

    def cond(carry):
        assign, remaining, k, live = carry
        return live & (k < n)

    def body(carry):
        assign, remaining, k, live = carry
        feas = ((assign < 0)[:, None] & eligible
                & (costs[:, None] <= remaining[None, :] + 1e-12))
        d = jnp.where(feas, density, -jnp.inf).reshape(-1)
        flat = (n * m - 1) - jnp.argmax(d[::-1])      # last max on ties
        ok = d[flat] > 0.0
        i, j = flat // m, flat % m
        assign = jnp.where(ok, assign.at[i].set(j.astype(assign.dtype)),
                           assign)
        remaining = jnp.where(ok, remaining.at[j].add(-costs[i]), remaining)
        return assign, remaining, k + 1, ok

    assign0 = jnp.full(n, -1, jnp.int32)
    carry = (assign0, budgets.astype(values.dtype), jnp.zeros((), jnp.int32),
             jnp.ones((), bool))
    with jax.named_scope(scopes.WALK):
        assign, _, _, _ = lax.while_loop(cond, body, carry)
    return assign


@partial(jax.jit, static_argnames=("num_es", "use_kernel", "tile",
                                   "interpret"))
def flgreedy_assign(values: jax.Array, costs: jax.Array, budgets: jax.Array,
                    eligible: jax.Array, num_es: int = 0,
                    use_kernel: Optional[bool] = None, tile: int = 0,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Cost-benefit greedy for the monotone submodular P3 (Eq. 19):
    utility(total) = sqrt(total / M). Exact (non-lazy) marginal gains."""
    use_k, _ = resolve_kernel_mode(use_kernel)
    if use_k:
        return flgreedy_topk(values, costs, budgets, eligible,
                             num_es=num_es, use_kernel=True, tile=tile,
                             interpret=interpret)
    n, m = values.shape
    m_div = float(num_es or m)

    def util(total):
        return jnp.sqrt(jnp.maximum(total, 0.0) / m_div)

    def cond(carry):
        assign, remaining, total, k, live = carry
        return live & (k < n)

    def body(carry):
        assign, remaining, total, k, live = carry
        gains = util(total + values) - util(total)          # (N, M)
        feas = ((assign < 0)[:, None] & eligible & (costs[:, None] > 0)
                & (costs[:, None] <= remaining[None, :] + 1e-12))
        d = jnp.where(feas, gains / jnp.maximum(costs[:, None], 1e-12),
                      -jnp.inf).reshape(-1)
        flat = (n * m - 1) - jnp.argmax(d[::-1])
        i, j = flat // m, flat % m
        ok = feas.reshape(-1)[flat] & (gains[i, j] > 1e-15)
        assign = jnp.where(ok, assign.at[i].set(j.astype(assign.dtype)),
                           assign)
        remaining = jnp.where(ok, remaining.at[j].add(-costs[i]), remaining)
        total = jnp.where(ok, total + values[i, j], total)
        return assign, remaining, total, k + 1, ok

    assign0 = jnp.full(n, -1, jnp.int32)
    carry = (assign0, budgets.astype(values.dtype),
             jnp.zeros((), values.dtype), jnp.zeros((), jnp.int32),
             jnp.ones((), bool))
    with jax.named_scope(scopes.WALK):
        assign, _, _, _, _ = lax.while_loop(cond, body, carry)
    return assign


@jax.jit
def random_assign(key: jax.Array, costs: jax.Array, budgets: jax.Array,
                  eligible: jax.Array) -> jax.Array:
    """Feasible random assignment: random client order, uniform choice among
    the ESs that are eligible and still have budget (Gumbel-argmax)."""
    n, m = eligible.shape
    kperm, kchoice = jax.random.split(key)
    order = jax.random.permutation(kperm, n)
    gumbel = jax.random.gumbel(kchoice, (n, m), costs.dtype)

    def step(carry, i):
        assign, remaining = carry
        feas = eligible[i] & (costs[i] <= remaining)
        j = jnp.argmax(jnp.where(feas, gumbel[i], -jnp.inf)).astype(jnp.int32)
        ok = feas.any()
        assign = jnp.where(ok, assign.at[i].set(j), assign)
        remaining = jnp.where(ok, remaining.at[j].add(-costs[i]), remaining)
        return (assign, remaining), None

    assign0 = jnp.full(n, -1, jnp.int32)
    (assign, _), _ = lax.scan(step, (assign0, budgets.astype(costs.dtype)),
                              order)
    return assign
