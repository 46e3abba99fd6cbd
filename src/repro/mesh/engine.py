"""The client-sharded fused tier-4 block: ``shard_map`` over the
``("seed", "clients")`` cohort mesh.

One jitted call still covers a whole eval interval for all seeds, with
the identical stage sequence as ``experiment.fused.fused_block_device``
— in-scan env generation, select/update, packing, local SGD, masked
aggregation, cloud sync, block-end eval — but every client-indexed
tensor lives as an ``(n_local, ...)`` shard:

* env generation consumes shard-local draw slices
  (``sim.draws.shard_round_draws``) — bitwise rows of the dense stream;
* selection runs the hierarchical merge walk (``repro.mesh.select``) —
  bitwise the dense greedy/flgreedy assignment;
* packing scatters shard rows at global slots and ``psum``s
  (``experiment.packing.pack_assignment_sharded``) — bitwise the dense
  pack, so the batch-sampling keys (slot-position addressed, sizes
  replicated) are unchanged;
* the per-slot training batches are assembled by an owner-masked gather
  + ``psum`` (each slot's client rows live on exactly one shard);
* training, aggregation, sync and eval run on the packed replicated
  ``(M, slots)`` cohort — identical work on every client shard, so the
  edge/global models match the dense block bitwise.

No dense ``(N, M)`` tensor is ever materialized: inside the shard_map
every client-axis intermediate is ``n_local``-sized, which
``tests/test_mesh_engine.py`` asserts on the jaxpr. Update-corruption
faults are the one unsupported fused feature (their slot mask gathers a
client-dense corruption vector); the factory rejects such specs.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.experiment.fused import BlockOut, _block_eval, _swap
from repro.experiment.packing import pack_assignment_sharded
from repro.fed.batched import (BatchedRoundSpec, device_batch_indices,
                               slot_train)
from repro.fed.edge import broadcast_global
from repro.fed.robust import robust_aggregate_stacked
from repro.kernels.common import resolve_kernel_mode
from repro.mesh.select import shard_assign
from repro.mesh.topology import cohort_mesh
from repro.obs import scopes
from repro.obs.telemetry import (TelemetryFrame, acc_init, acc_update,
                                 aggregator_adjusted)
from repro.policies.base import FunctionalPolicy
from repro.sim import draws
from repro.sim.core import sim_round


class ShardDims(NamedTuple):
    """Static shape facts of one sharded block instantiation."""
    num_clients: int     # global N
    n_local: int         # N / client_shards
    seed_shards: int
    client_shards: int


def _own(a, mask):
    """Zero the slot entries this shard does not own; ``mask`` (leading
    dims of ``a``) broadcasts over the trailing per-slot data dims."""
    return jnp.where(
        mask.reshape(mask.shape + (1,) * (a.ndim - mask.ndim)),
        a, jnp.zeros((), a.dtype))


def _mask_topz(arrived, tau, valid, z_min: int):
    """``fed.edge.effective_mask_multi`` without ``lax.top_k``.

    ``top_k`` lowers to ``lax.sort``, which the SPMD partitioner
    mis-partitions inside a ``check_vma=False`` shard_map body (see
    ``repro.mesh.select``); the Z-fastest fallback set is recovered
    instead by pairwise slot ranks — ``rank_i = #{j : (tau_j, j) <
    (tau_i, i)}`` — which reproduces ``top_k(-tau, z)``'s
    lower-index-first tie-breaking exactly, so the mask is bitwise the
    dense one. O(slots^2) per ES row; slots is the small packed
    capacity, not N."""
    valid = valid.astype(jnp.float32)
    arrived = arrived.astype(jnp.float32) * valid
    tau = jnp.where(valid > 0, tau, jnp.inf)
    count = jnp.sum(arrived, axis=1, keepdims=True)
    z = min(int(z_min), arrived.shape[1])
    idx = jnp.arange(arrived.shape[1])
    ti, tj = tau[:, :, None], tau[:, None, :]
    ahead = (tj < ti) | ((tj == ti) & (idx[None, None, :] < idx[None, :, None]))
    rank = jnp.sum(ahead, axis=2)
    fallback = (rank < z).astype(jnp.float32)
    return jnp.where(count >= z, arrived, fallback) * valid


def _shard_frame(policy, pstate, rd, assign, arrived, valid, deltas, w,
                 spec: BatchedRoundSpec, axis: str) -> TelemetryFrame:
    """``obs.telemetry.round_frame`` with the client-axis reductions
    psummed over the mesh: the policy tap and the selection/spend sums
    see shard rows; everything slot-shaped is already replicated. Same
    observables — but float sums reassociate across shards, so
    telemetry (unlike selections/utilities/models) matches the dense
    tap only to float tolerance."""
    b, m = assign.shape[0], w.shape[1]
    zeros = jnp.zeros((b,), jnp.float32)
    if hasattr(policy, "telemetry_sums"):
        sums = jax.vmap(policy.telemetry_sums)(pstate, rd)
        width_sum = lax.psum(sums["width_sum"], axis)
        n_el = jnp.maximum(lax.psum(sums["eligible"], axis), 1)
        ucb_width = width_sum / n_el
        under = lax.psum(sums["under"], axis).astype(jnp.float32)
    else:
        ucb_width, under = zeros, zeros
    sel_mask = assign >= 0
    selected = lax.psum(jnp.sum(sel_mask, axis=1), axis).astype(jnp.float32)
    costs = jnp.asarray(rd.costs, jnp.float32)
    spent = lax.psum(jnp.sum(jnp.where(sel_mask, costs, 0.0), axis=1), axis)
    total = jnp.full((b,), float(policy.spec.budget) * m, jnp.float32)
    budget_util = spent / jnp.maximum(total, 1e-12)
    v = valid > 0
    a = (arrived > 0) & v
    arrived_n = jnp.sum(a, axis=(1, 2)).astype(jnp.float32)
    miss = jnp.sum(v & ~a, axis=(1, 2)).astype(jnp.float32)
    slot_sq = zeros[:, None, None]
    for d in jax.tree.leaves(deltas):
        slot_sq = slot_sq + jnp.sum(
            jnp.square(d.astype(jnp.float32)),
            axis=tuple(range(3, d.ndim)))
    slot_norms = jnp.sqrt(slot_sq)
    wmask = (w > 0).astype(jnp.float32)
    delta_norm = jnp.sqrt(jnp.sum(slot_sq * wmask, axis=(1, 2)))
    adjusted = aggregator_adjusted(spec.aggregator, float(spec.trim_frac),
                                   w, slot_norms)
    return TelemetryFrame(ucb_width=ucb_width, underexplored=under,
                          budget_util=budget_util, selected=selected,
                          arrived=arrived_n, deadline_miss=miss,
                          delta_norm=delta_norm, agg_adjusted=adjusted,
                          corrupted=zeros)


@functools.lru_cache(maxsize=None)
def sharded_block_device(policy: FunctionalPolicy, spec: BatchedRoundSpec,
                         slots: int, batch: int, loss_fn, logits_fn,
                         sim_spec, dims: ShardDims,
                         telemetry: bool = False):
    """Compile-once sharded twin of ``fused_block_device``.

    Same signature — ``block(stacked_x, stacked_y, stacked_sizes,
    base_keys, policy_state, edge_params, env_pos, seeds, statics, ts,
    test_x, test_y) -> BlockOut`` — but the caller stages client-indexed
    inputs over ``"clients"`` and per-seed inputs over ``"seed"``
    (``mesh.topology.shard_layouts``); outputs come back with the same
    global layout, selections as the reassembled (S, T, N) axis.
    Requires a policy exposing ``pair_values``/``update`` row-local in
    the client axis (COCS) and no update-corruption faults.
    """
    if sim_spec.faults is not None and sim_spec.faults.corrupt_rate > 0.0:
        raise NotImplementedError(
            "update-corruption faults are not supported by the sharded "
            "cohort engine (client-dense corruption mask)")
    if not hasattr(policy, "pair_values"):
        raise NotImplementedError(
            f"policy {policy.name!r} exposes no row-local pair_values "
            "table; the sharded engine needs one to merge across shards")
    if spec.aggregator != "mean":
        raise NotImplementedError(
            f"aggregator {spec.aggregator!r} sorts per-coordinate slot "
            "cohorts; lax.sort is mis-partitioned inside the sharded "
            "block (see repro.mesh.select) -- use the dense tier for "
            "robust aggregation")
    mesh = cohort_mesh(dims.seed_shards, dims.client_shards)
    m, steps = spec.num_edge_servers, spec.steps
    sqrt_u = policy.spec.sqrt_utility
    n, n_local = dims.num_clients, dims.n_local
    k_mc = 0 if sim_spec.true_p == "analytic" else sim_spec.mc_true_p
    faulty = sim_spec.faults is not None and sim_spec.faults.enabled
    use_k, interp = resolve_kernel_mode(policy.use_kernel)

    def body(stacked_x, stacked_y, stacked_sizes, base_keys,
             policy_state, edge_params, env_pos, seeds, statics,
             ts, test_x, test_y):
        lo = lax.axis_index("clients") * n_local
        bud = jnp.asarray(policy.spec.budgets(), jnp.float32)

        def gen_round(seed, st, p, t):
            dr = draws.shard_round_draws(seed, t, n, m, k_mc, lo, n_local)
            fd = (draws.shard_fault_draws(seed, t, n, m, lo, n_local)
                  if faulty else None)
            return sim_round(sim_spec, seed, st, p, t, dr=dr, fd=fd)

        def select(pst, r):
            values, under = policy.pair_values(pst, r)
            assign = shard_assign(
                values, jnp.asarray(r.costs, values.dtype),
                jnp.asarray(r.eligible, bool), bud.astype(values.dtype),
                axis_name="clients", num_clients=n, sqrt_utility=sqrt_u,
                sync_axes=("seed",), use_kernel=use_k,
                tile=policy.kernel_tile, interpret=interp)
            return assign, under

        def step(carry, t):
            if telemetry:
                pstate, edge, pos, tacc = carry
            else:
                pstate, edge, pos = carry
            n_seeds = base_keys.shape[0]
            pos, sr = jax.vmap(
                lambda se, st, p: gen_round(se, st, p, t))(seeds, statics,
                                                           pos)
            rd = sr.round
            with jax.named_scope(scopes.SELECT):
                assign, under = jax.vmap(select)(pstate, rd)
                # the dense step's per-seed aux {"explored": under.any()},
                # OR-reduced over the mesh (the same global any)
                explored = lax.psum(
                    under.any(axis=(1, 2)).astype(jnp.int32), "clients") > 0
                new_pstate = jax.vmap(policy.update)(pstate, rd, assign,
                                                     {"explored": explored})
            with jax.named_scope(scopes.TRAIN):
                ci, valid, arrived, tau = jax.vmap(
                    lambda a, o, l: pack_assignment_sharded(
                        a, o, l, m, slots, "clients", lo))(
                            assign, rd.outcomes, rd.latency)
                idx = jax.vmap(device_batch_indices,
                               in_axes=(0, 0, 0, None, None, None))(
                    base_keys, rd.t, ci, stacked_sizes, steps, batch)
                # client-sharded data: each shard gathers the slots whose
                # client rows it owns and a psum assembles the replicated
                # slot batches — exactly one contributor per realized slot;
                # padding slots are client 0, owned by shard 0, the same
                # rows the dense gather pulls for them
                owns = (ci >= lo) & (ci < lo + n_local)
                cl = jnp.clip(ci - lo, 0, n_local - 1)
                xb = lax.psum(_own(stacked_x[cl[..., None, None], idx],
                                   owns[..., None, None]), "clients")
                yb = lax.psum(_own(stacked_y[cl[..., None, None], idx],
                                   owns[..., None, None]), "clients")
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
                w = _mask_topz(
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
            outs = (assign, util, parts, explored)
            if telemetry:
                frame = _shard_frame(policy, pstate, rd, assign, arrived,
                                     valid, deltas, w, spec, "clients")
                tacc = acc_update(tacc, frame, explored)
                return (new_pstate, new_edge, pos, tacc), outs + (frame,)
            return (new_pstate, new_edge, pos), outs

        init = ((policy_state, edge_params, env_pos,
                 acc_init(base_keys.shape[0]))
                if telemetry else (policy_state, edge_params, env_pos))
        carry, ys = lax.scan(step, init, ts)
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

    sc = P("seed", "clients")
    so = P("seed")
    cl = P("clients")
    rep = P()

    def _tree_spec(tree_proto, spec_):
        return jax.tree.map(lambda _: spec_, tree_proto)

    def block(stacked_x, stacked_y, stacked_sizes, base_keys,
              policy_state, edge_params, env_pos, seeds, statics,
              ts, test_x, test_y):
        specs_in = (cl, cl, rep, so,
                    _tree_spec(policy_state, sc),
                    _tree_spec(edge_params, so),
                    sc, so, _tree_spec(statics, sc),
                    rep, rep, rep)
        tele_frame_spec = (_tree_spec(
            TelemetryFrame(*([0] * len(TelemetryFrame._fields))), so)
            if telemetry else None)
        specs_out = BlockOut(
            policy_state=_tree_spec(policy_state, sc),
            edge_params=_tree_spec(edge_params, so),
            selections=P("seed", None, "clients"),
            utilities=so, participants=so, explored=so,
            accuracy=so, loss=so, env_pos=sc,
            telemetry=tele_frame_spec,
            tele_acc=(_tree_spec(acc_init(1), so) if telemetry else None))
        fn = jax.shard_map(body, mesh=mesh, in_specs=specs_in,
                           out_specs=specs_out, check_vma=False)
        return fn(stacked_x, stacked_y, stacked_sizes, base_keys,
                  policy_state, edge_params, env_pos, seeds, statics,
                  ts, test_x, test_y)

    return jax.jit(block, donate_argnums=(4, 5, 6))
