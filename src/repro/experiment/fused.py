"""The fused device-resident HFL block: policy + training + evaluation.

One jitted call covers an entire eval interval for *all seeds at once*:

    lax.scan over rounds of
        [env="device"] Eq. 4-6 context generation (repro.sim)     [env]
        select (P2/P3 solver)  ->  update (CC-MAB estimators)   [policy]
        traced packing         ->  on-device batch sampling
        Eq. 2 local SGD        ->  Eq. 6 deadline masks
        Eq. 3 masked aggregation -> cloud sync                  [training]
    then one batched test-set evaluation per block               [eval]

The seed axis is batched *explicitly* rather than with an outer
``jax.vmap``: the policy step is vmapped per stage, while the training
stages fold seeds into the existing batch axes — (S, M, slots) slots
flatten into one ``local_sgd_multi`` call and the aggregation routes
through ``masked_aggregate_stacked``'s (S, M, ...) path, so the Pallas
kernel sees ordinary stacked shapes instead of relying on batching rules.

Two block variants share one round body (``_train_round_step``):

* ``fused_block`` scans a host-realized ``Round`` batch with (T, S, ...)
  leaves — the env observables were stacked on host;
* ``fused_block_device`` scans a (T,) array of round indices and
  generates each round's observables *inside* the scan with
  ``repro.sim.core.round_batch`` — no pre-realization, no (S, T, ...)
  host arrays; the env's only carried state (mobility positions) rides
  in the block carry and flows between blocks via ``BlockOut.env_pos``.

Carries (policy state, edge params, env positions) are donated, so a
run's device residency is: one dispatch per eval interval, zero host
round-trips inside it.

Pallas kernel routing inside the block needs no parameters here: the
env stage honors ``SimSpec.use_kernel``/``kernel_tile`` (fused Eq. 4/5
``context_pairwise`` launch inside the scan) and the select stage honors
the policy dataclass's ``use_kernel`` (``budgeted_topk`` solver) — both
ride static arguments, and each resolves to a bitwise-identical jnp path
on CPU, so kernels-on blocks reproduce kernels-off decisions exactly.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.fed.batched import (BatchedRoundSpec, device_batch_indices,
                               slot_train)
from repro.fed.edge import broadcast_global, effective_mask_multi
from repro.fed.robust import robust_aggregate_stacked
from repro.experiment.packing import pack_assignment
from repro.models.logistic import accuracy, softmax_xent
from repro.obs import scopes
from repro.obs.telemetry import acc_init, acc_update, round_frame
from repro.policies.base import FunctionalPolicy


class BlockOut(NamedTuple):
    """Per-block device outputs (leading axes: S seeds, T block rounds)."""
    policy_state: object
    edge_params: object
    selections: jax.Array    # (S, T, N) int32
    utilities: jax.Array     # (S, T)
    participants: jax.Array  # (S, T)
    explored: jax.Array      # (S, T) bool
    accuracy: jax.Array      # (S,) test accuracy at block end
    loss: jax.Array          # (S,) test loss at block end
    env_pos: Optional[jax.Array] = None  # (S, N, 2) device-env carry
    # observability taps (telemetry=True variants only; repro.obs):
    telemetry: Optional[object] = None   # TelemetryFrame, (S, T) leaves
    tele_acc: Optional[object] = None    # TelemetryAcc, (S,) running totals


def _train_round_step(policy: FunctionalPolicy, spec: BatchedRoundSpec,
                      slots: int, batch: int, loss_fn, grid: bool = False,
                      faults=None, telemetry: bool = False):
    """One training round for all seeds: ``(pstate, edge, rd, data...) ->
    (pstate', edge', outs)``. Shared by the host-rounds and device-env
    block variants so the two paths cannot drift. With ``grid=True`` the
    batch axis enumerates flattened (config cell, seed) pairs and ``step``
    takes an extra (B,) per-element budget scalar, threaded into the
    solver through ``select_with_budgets`` — config axes batch exactly
    like seeds.

    ``faults`` (``repro.sim.faults.FaultSpec``) enables update
    corruption: each element's corruption events are re-derived in-scan
    from the counter-based schedule via its env seed (``env_seeds``), so
    the host-loop engine's packed events match bitwise, and the
    corrupted slots' deltas are scaled by ``corrupt_scale`` before the
    Eq. 3 aggregation (``spec.aggregator`` picks the rule).

    ``telemetry`` appends a fifth element to ``outs`` — a per-round
    ``repro.obs.telemetry.TelemetryFrame`` derived purely from the
    intermediates this step already computes (no RNG, no extra draws),
    so the existing outputs stay bitwise identical either way."""
    m, steps = spec.num_edge_servers, spec.steps
    sqrt_u = policy.spec.sqrt_utility
    corrupting = faults is not None and faults.corrupt_rate > 0.0

    def _select(pstate, rd, budgets):
        if grid:
            return jax.vmap(
                lambda st, r, b: policy.select_with_budgets(
                    st, r, jnp.full((m,), b, jnp.float32)))(
                        pstate, rd, budgets)
        return jax.vmap(policy.select)(pstate, rd)

    def step(pstate, edge, rd, stacked_x, stacked_y, stacked_sizes,
             base_keys, budgets=None, env_seeds=None):
        n_seeds = base_keys.shape[0]
        with jax.named_scope(scopes.SELECT):
            assign, aux = _select(pstate, rd, budgets)
            new_pstate = jax.vmap(policy.update)(pstate, rd, assign, aux)
        with jax.named_scope(scopes.TRAIN):
            ci, valid, arrived, tau = jax.vmap(
                pack_assignment, in_axes=(0, 0, 0, None, None))(
                    assign, rd.outcomes, rd.latency, m, slots)
            idx = jax.vmap(device_batch_indices,
                           in_axes=(0, 0, 0, None, None, None))(
                base_keys, rd.t, ci, stacked_sizes, steps, batch)
            xb = stacked_x[ci[..., None, None], idx]  # (S,M,slots,steps,B,..)
            yb = stacked_y[ci[..., None, None], idx]
            flat = n_seeds * m * slots
            batches = {
                "x": xb.reshape((flat, steps, batch) + xb.shape[5:]),
                "y": yb.reshape(flat, steps, batch),
            }
            slot_params = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a[:, :, None], (n_seeds, m, slots) + a.shape[2:]
                ).reshape((flat,) + a.shape[2:]), edge)
            deltas = slot_train(slot_params, batches,
                                valid.reshape(flat) > 0, spec, loss_fn)
            deltas = jax.tree.map(
                lambda d: d.reshape((n_seeds, m, slots) + d.shape[1:]),
                deltas)
        with jax.named_scope(scopes.AGGREGATE):
            slot_c = None
            if corrupting:
                from repro.sim import draws
                from repro.sim.faults import corrupt_mask
                n_clients = rd.eligible.shape[1]
                corr_u = jax.vmap(lambda se: draws.fault_draws(
                    se, rd.t[0], n_clients, m).corr_u)(env_seeds)   # (S, N)
                cmask = corrupt_mask(faults, corr_u, jnp)
                slot_c = jax.vmap(lambda cm, idx: cm[idx])(cmask, ci)
                scale = jnp.where(slot_c,
                                  jnp.float32(faults.corrupt_scale),
                                  jnp.float32(1.0))         # (S,M,slots)
                deltas = jax.tree.map(
                    lambda d: d * scale.reshape(
                        scale.shape + (1,) * (d.ndim - 3)), deltas)
            w = effective_mask_multi(
                arrived.reshape(n_seeds * m, slots),
                tau.reshape(n_seeds * m, slots),
                valid.reshape(n_seeds * m, slots),
                spec.z_min).reshape(n_seeds, m, slots)
            new_edge = robust_aggregate_stacked(
                edge, deltas, w, aggregator=spec.aggregator,
                trim_frac=spec.trim_frac, use_kernel=spec.use_kernel,
                tile=spec.tile, interpret=spec.interpret)
            sync = ((rd.t[0] + 1) % spec.t_es) == 0
            synced = jax.vmap(broadcast_global)(new_edge)
            new_edge = jax.tree.map(
                lambda a, c: jnp.where(sync, a, c), synced, new_edge)
        parts = jnp.sum(arrived * valid, axis=(1, 2))     # (S,)
        util = jnp.sqrt(parts / m) if sqrt_u else parts
        explored = (aux.get("explored",
                            jnp.zeros((n_seeds,), bool))
                    if isinstance(aux, dict)
                    else jnp.zeros((n_seeds,), bool))
        outs = (assign, util, parts, explored)
        if telemetry:
            frame = round_frame(policy, pstate, rd, assign, arrived,
                                valid, deltas, w, budgets, spec, slot_c)
            outs = outs + (frame,)
        return new_pstate, new_edge, outs

    return step


def _block_eval(logits_fn, edge, test_x, test_y):
    """Batched eval: global model per seed = mean over its M edge models."""
    with jax.named_scope(scopes.EVAL):
        global_params = jax.tree.map(lambda a: jnp.mean(a, axis=1), edge)
        logits = jax.vmap(lambda p: logits_fn(p, test_x))(global_params)
        acc = jax.vmap(accuracy, in_axes=(0, None))(logits, test_y)
        loss = jax.vmap(softmax_xent, in_axes=(0, None))(logits, test_y)
    return acc, loss


def _swap(a):
    # scan stacks per-round outputs on the leading axis: (T, S) -> (S, T)
    return jnp.swapaxes(a, 0, 1)


@functools.lru_cache(maxsize=None)
def fused_block(policy: FunctionalPolicy, spec: BatchedRoundSpec,
                slots: int, batch: int, loss_fn, logits_fn,
                faults=None, telemetry: bool = False):
    """Compile-once block runner for one (policy, spec, shapes) variant.

    Returns ``block(stacked_x, stacked_y, stacked_sizes, base_keys,
    policy_state, edge_params, rounds, test_x, test_y, env_seeds) ->
    BlockOut`` where ``rounds`` is a ``Round`` pytree with (T, S, ...)
    leaves (scan axis first), ``base_keys`` is (S,) per-seed PRNG keys,
    ``env_seeds`` is the (S,) uint32 env-seed vector (consumed only when
    ``faults`` enables update corruption) and the carries have a leading
    (S,) seed axis. Cached on value-hashable statics so every sweep over
    an equivalent configuration shares one executable.

    ``telemetry`` threads a ``TelemetryAcc`` through the scan carry and
    stacks per-round ``TelemetryFrame``s into ``BlockOut.telemetry`` —
    pure extra outputs, so the original streams are bitwise unchanged.
    """
    round_step = _train_round_step(policy, spec, slots, batch, loss_fn,
                                   faults=faults, telemetry=telemetry)

    def block(stacked_x, stacked_y, stacked_sizes, base_keys,
              policy_state, edge_params, rounds, test_x, test_y,
              env_seeds):

        def step(carry, rd):
            if telemetry:
                pstate, edge, tacc = carry
            else:
                pstate, edge = carry
            pstate, edge, outs = round_step(pstate, edge, rd, stacked_x,
                                            stacked_y, stacked_sizes,
                                            base_keys,
                                            env_seeds=env_seeds)
            if telemetry:
                tacc = acc_update(tacc, outs[4], outs[3])
                return (pstate, edge, tacc), outs
            return (pstate, edge), outs

        init = ((policy_state, edge_params,
                 acc_init(base_keys.shape[0]))
                if telemetry else (policy_state, edge_params))
        carry, ys = jax.lax.scan(step, init, rounds)
        pstate, edge = carry[0], carry[1]
        sel, util, parts, explored = ys[:4]
        acc, loss = _block_eval(logits_fn, edge, test_x, test_y)
        return BlockOut(
            policy_state=pstate, edge_params=edge,
            selections=_swap(sel), utilities=_swap(util),
            participants=_swap(parts), explored=_swap(explored),
            accuracy=acc, loss=loss,
            telemetry=(jax.tree.map(_swap, ys[4]) if telemetry else None),
            tele_acc=(carry[2] if telemetry else None))

    return jax.jit(block, donate_argnums=(4, 5))


@functools.lru_cache(maxsize=None)
def fused_block_device(policy: FunctionalPolicy, spec: BatchedRoundSpec,
                       slots: int, batch: int, loss_fn, logits_fn,
                       sim_spec, telemetry: bool = False):
    """``fused_block`` with the environment *inside* the compiled region.

    Returns ``block(stacked_x, stacked_y, stacked_sizes, base_keys,
    policy_state, edge_params, env_pos, seeds, statics, ts, test_x,
    test_y) -> BlockOut``: ``ts`` is the (T,) int32 array of round
    indices this block covers, ``seeds``/``statics``/``env_pos`` carry
    the per-seed env identity and mobility state (leading (S,) axis).
    Each scan step realizes its round with ``repro.sim`` before the
    shared policy+training body runs — no host-realized observables.
    Fault injection rides ``sim_spec.faults``: the env stage injects
    dropout/straggler/outage, and update corruption is derived in-scan
    from the same ``seeds`` the env consumes.
    """
    from repro.sim.core import round_batch
    round_step = _train_round_step(policy, spec, slots, batch, loss_fn,
                                   faults=sim_spec.faults,
                                   telemetry=telemetry)

    def block(stacked_x, stacked_y, stacked_sizes, base_keys,
              policy_state, edge_params, env_pos, seeds, statics,
              ts, test_x, test_y):

        def step(carry, t):
            if telemetry:
                pstate, edge, pos, tacc = carry
            else:
                pstate, edge, pos = carry
            pos, rd = round_batch(sim_spec, seeds, statics, pos, t)
            pstate, edge, outs = round_step(pstate, edge, rd, stacked_x,
                                            stacked_y, stacked_sizes,
                                            base_keys, env_seeds=seeds)
            if telemetry:
                tacc = acc_update(tacc, outs[4], outs[3])
                return (pstate, edge, pos, tacc), outs
            return (pstate, edge, pos), outs

        init = ((policy_state, edge_params, env_pos,
                 acc_init(base_keys.shape[0]))
                if telemetry else (policy_state, edge_params, env_pos))
        carry, ys = jax.lax.scan(step, init, ts)
        pstate, edge, pos = carry[0], carry[1], carry[2]
        sel, util, parts, explored = ys[:4]
        acc, loss = _block_eval(logits_fn, edge, test_x, test_y)
        return BlockOut(
            policy_state=pstate, edge_params=edge,
            selections=_swap(sel), utilities=_swap(util),
            participants=_swap(parts), explored=_swap(explored),
            accuracy=acc, loss=loss, env_pos=pos,
            telemetry=(jax.tree.map(_swap, ys[4]) if telemetry else None),
            tele_acc=(carry[3] if telemetry else None))

    return jax.jit(block, donate_argnums=(4, 5, 6))


@functools.lru_cache(maxsize=None)
def fused_block_grid(policy: FunctionalPolicy, spec: BatchedRoundSpec,
                     slots: int, batch: int, loss_fn, logits_fn,
                     faults=None):
    """``fused_block`` over a flattened (config cell x seed) batch axis.

    Same signature plus a trailing ``budgets`` (B,) argument: one per-ES
    budget scalar per batch element, traced into the selection solver
    (``env_seeds`` is (B,) here — each cell repeats its seed's env).
    Deadline cells need no extra argument here — a host-realized grid
    batch already carries per-cell outcomes (recomputed in float64 on
    host before stacking, so a cell is bitwise the rounds a sequential
    run with that deadline would realize).
    """
    round_step = _train_round_step(policy, spec, slots, batch, loss_fn,
                                   grid=True, faults=faults)

    def block(stacked_x, stacked_y, stacked_sizes, base_keys,
              policy_state, edge_params, rounds, test_x, test_y, budgets,
              env_seeds):

        def step(carry, rd):
            pstate, edge = carry
            pstate, edge, outs = round_step(pstate, edge, rd, stacked_x,
                                            stacked_y, stacked_sizes,
                                            base_keys, budgets,
                                            env_seeds=env_seeds)
            return (pstate, edge), outs

        (pstate, edge), (sel, util, parts, explored) = jax.lax.scan(
            step, (policy_state, edge_params), rounds)
        acc, loss = _block_eval(logits_fn, edge, test_x, test_y)
        return BlockOut(
            policy_state=pstate, edge_params=edge,
            selections=_swap(sel), utilities=_swap(util),
            participants=_swap(parts), explored=_swap(explored),
            accuracy=acc, loss=loss)

    return jax.jit(block, donate_argnums=(4, 5))


@functools.lru_cache(maxsize=None)
def fused_block_device_grid(policy: FunctionalPolicy,
                            spec: BatchedRoundSpec, slots: int, batch: int,
                            loss_fn, logits_fn, sim_spec):
    """``fused_block_device`` over a flattened (config cell x seed) batch.

    Takes trailing ``budgets`` (B,) and ``deadlines`` (B,) arguments. The
    env is generated in-scan from per-element (seed, statics, pos) — a
    config cell reuses its seed's env — and each element's Eq. 6 outcomes
    are re-thresholded against its own deadline from the realized Eq. 5
    latencies, the identical float32 comparison a sequential run with
    that ``SimSpec.deadline_s`` would perform (bitwise-equal outcomes).
    """
    from repro.sim.core import round_batch
    round_step = _train_round_step(policy, spec, slots, batch, loss_fn,
                                   grid=True, faults=sim_spec.faults)

    def block(stacked_x, stacked_y, stacked_sizes, base_keys,
              policy_state, edge_params, env_pos, seeds, statics,
              ts, test_x, test_y, budgets, deadlines):

        def step(carry, t):
            pstate, edge, pos = carry
            pos, rd = round_batch(sim_spec, seeds, statics, pos, t)
            rd = rd._replace(outcomes=(
                rd.latency <= deadlines[:, None, None]
            ).astype(jnp.float32))
            pstate, edge, outs = round_step(pstate, edge, rd, stacked_x,
                                            stacked_y, stacked_sizes,
                                            base_keys, budgets,
                                            env_seeds=seeds)
            return (pstate, edge, pos), outs

        (pstate, edge, pos), (sel, util, parts, explored) = jax.lax.scan(
            step, (policy_state, edge_params, env_pos), ts)
        acc, loss = _block_eval(logits_fn, edge, test_x, test_y)
        return BlockOut(
            policy_state=pstate, edge_params=edge,
            selections=_swap(sel), utilities=_swap(util),
            participants=_swap(parts), explored=_swap(explored),
            accuracy=acc, loss=loss, env_pos=pos)

    return jax.jit(block, donate_argnums=(4, 5, 6))
