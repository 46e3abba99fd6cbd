"""Jitted bandit engine: lax.scan over rounds x vmap over seeds.

The environment realizes each round's observables on host (numpy — see
``repro.envs``); the engine stacks them into a ``Round`` pytree with a
leading T (and optionally S, for seeds) axis and runs the whole
policy loop — select, update, utility accounting — as one compiled
program per (policy config, horizon) pair. For jax-capable policies this
replaces the sequential Python per-round driver; host policies fall back
to the legacy loop via ``PolicyAdapter``.

This engine covers *bandit-only* runs (no training in the loop). The
device-resident experiment engine (``repro.experiment``) fuses the same
select/update step into the HFL training scan; it reuses
``stack_states`` / ``traced_utility`` below, and ``run_rounds_host``
stays the bitwise parity oracle for both.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.network import RoundData
from repro.core.utility import realized_utility
from repro.obs import scopes
from repro.policies.base import (FunctionalPolicy, PolicyAdapter, Round,
                                 stack_rounds)


def traced_utility(assign, outcomes, num_es: int, sqrt_utility: bool):
    """Eq. 7-8 / Eq. 19 realized utility as a traced function.

    Returns (utility, participants); shared by the bandit scan below and
    the fused experiment engine so the accounting cannot drift from
    ``repro.core.utility.realized_utility``.
    """
    n = assign.shape[0]
    sel = assign >= 0
    j = jnp.clip(assign, 0, num_es - 1)
    arrived = jnp.where(sel, outcomes[jnp.arange(n), j], 0.0)
    part = jnp.sum(arrived)
    if sqrt_utility:
        return jnp.sqrt(jnp.maximum(part, 0.0) / num_es), part
    return part, part


def stack_states(policy: FunctionalPolicy, seeds: Sequence[int]):
    """Per-seed initial states stacked along a leading S axis."""
    states = [policy.init(s) for s in seeds]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def policy_scan_step(policy: FunctionalPolicy, budgets=None):
    """The one-round policy body shared by every scanned engine:
    ``(state, rd) -> (state', (assign, utility, participants, explored))``.
    Used by the bandit scan below, and by the device-env bandit engine
    (``repro.sim.engine``) where ``rd`` is generated in-scan instead of
    read from a stacked batch. ``budgets`` optionally supplies the (M,)
    per-ES budget vector as a traced value (``select_with_budgets``) —
    the grid engines' batched-config path — instead of the policy's
    baked-in ``spec.budgets()``."""

    def step(state, rd: Round):
        with jax.named_scope(scopes.SELECT):
            if budgets is None:
                assign, aux = policy.select(state, rd)
            else:
                assign, aux = policy.select_with_budgets(state, rd,
                                                         budgets)
            new_state = policy.update(state, rd, assign, aux)
        util, part = traced_utility(assign, rd.outcomes,
                                    policy.spec.num_edge_servers,
                                    policy.spec.sqrt_utility)
        explored = aux.get("explored", jnp.zeros((), bool))
        return new_state, (assign, util, part, explored)

    return step


def _scan_fn(policy: FunctionalPolicy):
    """One compiled scan over a (T, ...) Round batch for one policy."""
    step = policy_scan_step(policy)

    def run(state0, batch: Round):
        final, (assigns, utils, parts, explored) = jax.lax.scan(
            step, state0, batch)
        return {"selections": assigns, "utilities": utils,
                "participants": parts, "explored": explored,
                "final_state": final}

    return run


def _grid_scan_fn(policy: FunctionalPolicy):
    """``_scan_fn`` with a per-run scalar budget: the budget rides as a
    traced argument (``select_with_budgets``) instead of a baked constant,
    so vmapping this function batches *config cells* exactly like seeds —
    the engine behind ``repro.api`` grids and their fused pre-scans."""
    num_es = policy.spec.num_edge_servers

    def run(state0, batch: Round, budget):
        step = policy_scan_step(
            policy, jnp.full((num_es,), budget, jnp.float32))
        final, (assigns, utils, parts, explored) = jax.lax.scan(
            step, state0, batch)
        return {"selections": assigns, "utilities": utils,
                "participants": parts, "explored": explored,
                "final_state": final}

    return run


@functools.lru_cache(maxsize=64)
def _compiled_grid(policy: FunctionalPolicy):
    return jax.jit(jax.vmap(_grid_scan_fn(policy)))


def run_rounds_grid(policy: FunctionalPolicy, batch: Round, budgets,
                    policy_seeds: Sequence[int]) -> Dict[str, np.ndarray]:
    """Batched bandit runs over config cells x seeds in one dispatch.

    ``batch`` is a ``Round`` pytree with (B, T, ...) leaves where B
    enumerates flattened (config cell, seed) pairs — each element carries
    its *own* realized rounds (a deadline axis changes the outcomes) —
    and ``budgets`` is the matching (B,) per-ES budget scalar. Returns
    host arrays with the leading B axis; jax-capable policies only.
    """
    if not policy.jax_capable:
        raise ValueError(f"{policy.name} is a host policy; grid batching "
                         "requires jax_capable select/update")
    assert batch.costs.shape[0] == len(policy_seeds)
    state0 = stack_states(policy, policy_seeds)
    out = _compiled_grid(policy)(
        state0, batch, jnp.asarray(np.asarray(budgets, np.float32)))
    return {k: np.asarray(v) if k != "final_state" else v
            for k, v in out.items()}


def _grid_scan_fn_params(policy: FunctionalPolicy):
    """``_grid_scan_fn`` for COCS hypercube axes: the resolution ``h_t``
    and Theorem-2 exponent ``z`` ride as per-run traced scalars
    (``select_with_params``/``update_with_params``) over a state padded
    to a shared ``h_pad`` lattice, so vmapping batches (h_t, alpha)
    config cells exactly like budgets and seeds."""
    num_es = policy.spec.num_edge_servers
    sqrt_utility = policy.spec.sqrt_utility

    def run(state0, batch: Round, budget, h, z):
        budgets = jnp.full((num_es,), budget, jnp.float32)

        def step(state, rd: Round):
            assign, aux = policy.select_with_params(state, rd, budgets,
                                                    h, z)
            new_state = policy.update_with_params(state, rd, assign, h)
            util, part = traced_utility(assign, rd.outcomes, num_es,
                                        sqrt_utility)
            explored = aux.get("explored", jnp.zeros((), bool))
            return new_state, (assign, util, part, explored)

        final, (assigns, utils, parts, explored) = jax.lax.scan(
            step, state0, batch)
        return {"selections": assigns, "utilities": utils,
                "participants": parts, "explored": explored,
                "final_state": final}

    return run


@functools.lru_cache(maxsize=64)
def _compiled_grid_params(policy: FunctionalPolicy):
    return jax.jit(jax.vmap(_grid_scan_fn_params(policy)))


def run_rounds_grid_params(policy: FunctionalPolicy, batch: Round, budgets,
                           hs, zs, policy_seeds: Sequence[int]
                           ) -> Dict[str, np.ndarray]:
    """``run_rounds_grid`` with per-element hypercube parameters.

    ``hs``/``zs`` are (B,) arrays of the COCS resolution/exponent for
    each flattened (config cell, seed) element; the state is allocated
    at ``h_pad = max(hs)`` and every element's cube indices stay inside
    its own ``h``-lattice, so each element is bitwise the sequential run
    with its parameters baked in. ``policy`` supplies the shared knobs
    (``k_scale``, ``bonus_scale``, solver choice); its own ``h_t``/
    ``alpha``/``z`` fields are ignored in favor of ``hs``/``zs``.
    """
    if not policy.jax_capable:
        raise ValueError(f"{policy.name} is a host policy; grid batching "
                         "requires jax_capable select/update")
    hs = np.asarray(hs, np.int32)
    assert batch.costs.shape[0] == len(policy_seeds) == len(hs)
    h_pad = int(hs.max())
    state0 = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[policy.init_padded(h_pad) for _ in policy_seeds])
    out = _compiled_grid_params(policy)(
        state0, batch, jnp.asarray(np.asarray(budgets, np.float32)),
        jnp.asarray(hs), jnp.asarray(np.asarray(zs, np.float32)))
    return {k: np.asarray(v) if k != "final_state" else v
            for k, v in out.items()}


@functools.lru_cache(maxsize=64)
def _compiled(policy: FunctionalPolicy, multi_seed: bool):
    run = _scan_fn(policy)
    if multi_seed:
        run = jax.vmap(run)
    return jax.jit(run)


def run_rounds(policy: FunctionalPolicy, rounds: Sequence[RoundData],
               seed: int = 0) -> Dict[str, np.ndarray]:
    """Single-seed scan over precomputed rounds. Returns host arrays."""
    if not policy.jax_capable:
        return run_rounds_host(policy, rounds, seed)
    batch = stack_rounds(rounds)
    state0 = policy.init(seed, rd0=rounds[0])
    out = _compiled(policy, False)(state0, batch)
    return {k: np.asarray(v) if k != "final_state" else v
            for k, v in out.items()}


def stack_rounds_multi(rounds_per_seed: Sequence[Sequence[RoundData]]
                       ) -> Round:
    """S lists of T RoundData -> one Round batch with (S, T, ...) arrays.

    Stack once and reuse across policies: the stacking is host-side data
    preparation, the engine proper is the compiled scan/vmap program.
    """
    batches = [stack_rounds(r) for r in rounds_per_seed]
    return Round(*(np.stack([getattr(b, f) for b in batches])
                   for f in Round._fields))


def run_rounds_multi_seed(policy: FunctionalPolicy,
                          rounds_per_seed,
                          seeds: Sequence[int]) -> Dict[str, np.ndarray]:
    """vmap over seeds: rounds_per_seed is S lists of T rounds (or an
    already-stacked ``Round`` batch from ``stack_rounds_multi``); returns
    arrays with a leading S axis. jax-capable policies only."""
    if not policy.jax_capable:
        raise ValueError(f"{policy.name} is a host policy; vmap over seeds "
                         "requires jax_capable select/update")
    batch = (rounds_per_seed if isinstance(rounds_per_seed, Round)
             else stack_rounds_multi(rounds_per_seed))
    assert batch.costs.shape[0] == len(seeds)
    state0 = stack_states(policy, seeds)
    out = _compiled(policy, True)(state0, batch)
    return {k: np.asarray(v) if k != "final_state" else v
            for k, v in out.items()}


def run_rounds_host(policy: FunctionalPolicy, rounds: Sequence[RoundData],
                    seed: int = 0) -> Dict[str, np.ndarray]:
    """Reference sequential driver (legacy semantics) for any policy."""
    adapter = PolicyAdapter(policy, seed=seed)
    t_len = len(rounds)
    n = policy.spec.num_clients
    selections = np.zeros((t_len, n), np.int64)
    utils = np.zeros(t_len)
    parts = np.zeros(t_len)
    explored = np.zeros(t_len, bool)
    for t, rd in enumerate(rounds):
        assign = adapter.select(rd)
        adapter.update(rd, assign)
        utils[t] = realized_utility(assign, rd, policy.spec.sqrt_utility)
        parts[t] = realized_utility(assign, rd, False)
        selections[t] = assign
        explored[t] = adapter.last_explored
    return {"selections": selections, "utilities": utils,
            "participants": parts, "explored": explored,
            "final_state": adapter.state}
