"""Sweep driver for the client-sharded cohort engine (tier 4 on a mesh).

``sweep_sharded`` is the ``experiment.sweep`` twin for runs whose
``ShardSpec`` splits the client and/or seed axis over a device mesh: it
stages every input with its mesh layout (``topology.shard_layouts``)
and dispatches ``mesh.engine.sharded_block_device`` per eval interval.
Selections, utilities, participants, policy/edge state and accuracy are
bitwise the dense tier-4 run (property- and parity-tested); telemetry
matches to float tolerance (cross-shard sum reassociation).

Scale notes: slot capacity comes from the analytic budget bound
(``slot_capacity``), not the dense bandit pre-scan — a pre-scan would
materialize the (N,) policy walk the mesh exists to avoid. Synthetic
fallback data is ``prepare_training``'s, shared with the dense tiers
(the 16-d ``"tiny"`` kind at metropolis scale); the returned selections
are still dense (S, T, N) on host, which at 10^6 clients is the
dominant host allocation (~0.8 GB per 200 rounds) — slice horizons
accordingly.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.federated import FederatedDataset
from repro.experiment.packing import slot_capacity
from repro.experiment.sweep import (SweepResult, _block_bounds,
                                    _collect_blocks, _traced_block,
                                    prepare_training)
from repro.mesh.engine import ShardDims, sharded_block_device
from repro.mesh.topology import cohort_mesh, shard_layouts
from repro.obs import trace as obs_trace
from repro.policies.base import FunctionalPolicy
from repro.policies.engine import stack_states

def _validate(env, shard, num_clients: int, n_seeds: int, model_kind: str):
    from repro.sim.core import DeviceEnv
    if not isinstance(env, DeviceEnv):
        raise ValueError(
            "the sharded cohort engine runs the device-env fused tier "
            f"(tier 4) only; got a {type(env).__name__} — build the env "
            "with backend='device' or drop the ShardSpec")
    if num_clients % shard.clients != 0:
        raise ValueError(
            f"ShardSpec.clients={shard.clients} must divide "
            f"num_clients={num_clients} (pad the cohort or pick a "
            "divisor shard count)")
    if n_seeds % shard.seeds != 0:
        raise ValueError(
            f"ShardSpec.seeds={shard.seeds} must divide the "
            f"{n_seeds} experiment seeds")
    if "moe" in model_kind.lower():
        raise NotImplementedError(
            "MoE models route tokens through lax.top_k/argsort, which "
            "the SPMD partitioner mis-partitions inside the sharded "
            "block (see repro.mesh.select); use the dense tier")


def sweep_sharded(policies: Dict[str, FunctionalPolicy], env,
                  seeds: Sequence[int], horizon: int, *, shard,
                  model_kind: str = "logreg", batch_size: int = 32,
                  batches_per_epoch: int = 2, eval_every: int = 5,
                  data: Optional[FederatedDataset] = None,
                  slots_per_es: Optional[int] = None,
                  policy_seed_offset: int = 0,
                  aggregator: str = "mean", trim_frac: float = 0.1,
                  telemetry: bool = False) -> SweepResult:
    """Run jax-capable policies over ``horizon`` rounds on the cohort
    mesh. Same contract as ``sweep_experiments`` restricted to the
    device-env fused tier; ``shard`` is the ``api.ShardSpec`` naming the
    ``("seed", "clients")`` mesh shape. Raises with the XLA_FLAGS hint
    when the mesh wants more devices than are visible."""
    cfg = getattr(env, "cfg", None)
    if cfg is None:
        raise ValueError("sweep_sharded needs a resolved DeviceEnv")
    seeds = [int(s) for s in seeds]
    _validate(env, shard, cfg.num_clients, len(seeds), model_kind)
    mesh = cohort_mesh(shard.seeds, shard.clients)
    dims = ShardDims(num_clients=cfg.num_clients,
                     n_local=cfg.num_clients // shard.clients,
                     seed_shards=shard.seeds, client_shards=shard.clients)
    pol_seeds = [s + int(policy_seed_offset) for s in seeds]

    with obs_trace.span("train.prepare", seeds=len(seeds),
                        model=model_kind, sharded=True):
        setup = prepare_training(cfg, model_kind, batch_size,
                                 batches_per_epoch, data, seeds,
                                 aggregator=aggregator,
                                 trim_frac=trim_frac)
    from repro import sim as simmod
    statics = simmod.init_statics_multi(env.spec, seeds)
    env_seeds = jnp.asarray(np.asarray(seeds, np.uint32))
    ends = _block_bounds(horizon, eval_every)

    result = SweepResult(policies=list(policies), seeds=seeds,
                         eval_rounds=np.asarray(ends), accuracy={},
                         loss={}, utilities={}, participants={},
                         selections={}, explored={}, health={},
                         telemetry={})
    for name, pol in policies.items():
        if not pol.jax_capable:
            raise ValueError(
                f"policy {name!r} is host-loop; the sharded engine "
                "fuses device scans only")
        slots = (int(slots_per_es) if slots_per_es is not None
                 else slot_capacity(pol.spec.budget, env.spec.min_cost(),
                                    cfg.num_clients))
        pstate = stack_states(pol, pol_seeds)
        with obs_trace.span("mesh.stage", policy=name,
                            mesh=f"{shard.seeds}x{shard.clients}"):
            sc, so, cl, rep = shard_layouts(
                mesh,
                seed_client=(pstate, statics),
                seed_only=(setup.base_keys, setup.edge_seed, env_seeds),
                client_only=(setup.stacked.x, setup.stacked.y),
                replicated=(setup.stacked.sizes, setup.test_x,
                            setup.test_y))
            pstate, statics_d = jax.device_put((pstate, statics), sc)
            base_keys, edge0, env_seeds_d = jax.device_put(
                (setup.base_keys, setup.edge_seed, env_seeds), so)
            sx, sy = jax.device_put(
                (setup.stacked.x, setup.stacked.y), cl)
            sizes, test_x, test_y = jax.device_put(
                (setup.stacked.sizes, setup.test_x, setup.test_y), rep)
            # pstate/edge/pos are donated carries: copy anything whose
            # buffer is shared with a non-donated arg (statics.pos0) or
            # reused for the next policy (edge_seed)
            edge = jax.tree.map(jnp.copy, edge0)
            pos = jnp.copy(statics_d.pos0)
        outs, lo = [], 0
        for bi, hi in enumerate(ends):

            def make_args(lo=lo, hi=hi, pstate=pstate, edge=edge,
                          pos=pos):
                fn = sharded_block_device(pol, setup.spec, slots,
                                          setup.batch, setup.loss_fn,
                                          setup.logits_fn, env.spec,
                                          dims, telemetry)
                return fn, (sx, sy, sizes, base_keys, pstate, edge, pos,
                            env_seeds_d, statics_d,
                            jnp.arange(lo, hi, dtype=jnp.int32),
                            test_x, test_y)

            out = _traced_block(sharded_block_device, make_args, bi, hi,
                                lo, slots, {"suffix": "_sharded",
                                            "policy": name})
            pstate, edge, pos = (out.policy_state, out.edge_params,
                                 out.env_pos)
            outs.append(out)
            lo = hi
        (result.accuracy[name], result.loss[name],
         result.utilities[name], result.participants[name],
         result.selections[name], result.explored[name],
         result.telemetry[name]) = _collect_blocks(outs, telemetry)
    return result
