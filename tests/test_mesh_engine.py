"""Live multi-device contract of the sharded cohort engine.

Runs only under a mesh with >= 8 devices (CI forces one on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``); the default
single-device suite skips it. Three pins:

* the full ``repro.run`` facade with a ``ShardSpec`` is bitwise the
  dense tier-4 run (selections through accuracy) on a real mesh;
* the metropolis-100k preset runs end to end through the sharded tier;
* the sharded block's jaxpr materializes **no unsharded (N, M)
  tensor** — the capacity claim, checked structurally: every dense
  client-pair table stays (N/shards, M), while the dense tier's jaxpr
  (the control) is full of (N, M) intermediates.
"""
import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import api

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs >= 8 devices (XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")

OVR = {"num_clients": 64, "num_edge_servers": 4, "h_t": 3}


def _spec(shard=None, **kw):
    base = dict(policy=api.PolicySpec("cocs"),
                env=api.EnvSpec("metropolis-1k",
                                config="mnist-metropolis-1k",
                                overrides=OVR, true_p="analytic"),
                train=api.TrainSpec(batch_size=16),
                eval=api.EvalSpec(eval_every=2),
                horizon=4, seeds=(0, 1), shard=shard)
    base.update(kw)
    return api.ExperimentSpec(**base)


FIELDS = ("selections", "utilities", "participants", "explored",
          "accuracy", "loss")


@needs_mesh
def test_sharded_run_bitwise_matches_dense():
    dense = repro.run(_spec())
    assert dense.tier == 4
    for cl, sd in ((4, 1), (4, 2)):
        res = repro.run(_spec(api.ShardSpec(clients=cl, seeds=sd)))
        assert res.tier == 4
        for f in FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(dense, f)),
                np.asarray(getattr(res, f)),
                err_msg=f"shard ({cl},{sd}) field {f}")


@needs_mesh
def test_metropolis_100k_end_to_end():
    res = repro.run(api.ExperimentSpec(
        policy=api.PolicySpec("cocs"),
        env=api.EnvSpec("metropolis-100k", true_p="analytic"),
        train=api.TrainSpec(batch_size=16),
        eval=api.EvalSpec(eval_every=2), horizon=2, seeds=(0,),
        shard=api.ShardSpec(clients=8),
        obs=repro.obs.ObsSpec(telemetry=True)))
    assert res.selections.shape == (1, 2, 100_000)
    assert np.asarray(res.participants).max() > 0
    assert np.all(np.isfinite(np.asarray(res.accuracy)))
    util = np.asarray(res.telemetry["series"]["budget_util"])
    assert util.shape == (1, 2) and float(util.max()) <= 1.0 + 1e-6


# -- jaxpr capacity contract -------------------------------------------------


def _iter_jaxprs(val):
    if isinstance(val, jax.extend.core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jax.extend.core.Jaxpr):
        yield val
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _iter_jaxprs(v)


def _dense_pair_vars(jaxpr, n, m, hits):
    """Collect vars shaped like client-pair tables: (n, m) 2-D (fading,
    eligibility, candidate values) or (k, n, m) 3-D (MC true-p draws).
    Higher-rank training tensors whose leading dims collide numerically
    are not pair tables and are excluded by construction."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            shape = tuple(getattr(getattr(v, "aval", None), "shape", ()))
            if shape == (n, m) or (len(shape) == 3 and shape[1:] == (n, m)):
                hits.append((eqn.primitive.name, shape))
        for pv in eqn.params.values():
            for sub in _iter_jaxprs(pv):
                _dense_pair_vars(sub, n, m, hits)


def _block_jaxpr(shard_clients):
    from repro.api.run import build_env, build_policy
    from repro.experiment.sweep import prepare_training
    from repro.mesh.engine import ShardDims, sharded_block_device
    from repro.policies.engine import stack_states
    from repro.sim.core import init_statics_multi

    spec = _spec(api.ShardSpec(clients=shard_clients))
    env = build_env(spec.env)
    pol = build_policy(spec.policy, env.cfg, spec.horizon)
    setup = prepare_training(env.cfg, "logreg", 16, 2, None, [0, 1])
    statics = init_statics_multi(env.spec, [0, 1])
    dims = ShardDims(num_clients=env.cfg.num_clients,
                     n_local=env.cfg.num_clients // shard_clients,
                     seed_shards=1, client_shards=shard_clients)
    fn = sharded_block_device(pol, setup.spec, 6, setup.batch,
                              setup.loss_fn, setup.logits_fn, env.spec,
                              dims)
    pstate = stack_states(pol, [0, 1])
    args = (setup.stacked.x, setup.stacked.y, setup.stacked.sizes,
            setup.base_keys, pstate, setup.edge_seed, statics.pos0,
            jnp.asarray(np.array([0, 1], np.uint32)), statics,
            jnp.arange(0, 2, dtype=jnp.int32), setup.test_x, setup.test_y)
    return jax.make_jaxpr(fn)(*args), env.cfg


@needs_mesh
def test_no_unsharded_pair_tensor_in_jaxpr():
    """Capacity contract: with the client axis split, no equation in the
    sharded block's jaxpr (sub-jaxprs included) produces a dense
    (N, M)-leading tensor; every pair table is (N/shards, M). The dense
    fused block is the control — its jaxpr is full of them."""
    closed, cfg = _block_jaxpr(4)
    n, m = cfg.num_clients, cfg.num_edge_servers
    hits = []
    _dense_pair_vars(closed.jaxpr, n, m, hits)
    assert not hits, f"unsharded (N, M) tensors in sharded block: {hits}"
    local = []
    _dense_pair_vars(closed.jaxpr, n // 4, m, local)
    assert local, "expected shard-local (N/shards, M) pair tables"

    closed1, _ = _block_jaxpr(1)     # control: unsharded mesh
    dense_hits = []
    _dense_pair_vars(closed1.jaxpr, n, m, dense_hits)
    assert dense_hits, "control run should materialize (N, M) tables"
