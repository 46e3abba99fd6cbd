"""HFL runtime: Eq. (6) aggregation semantics, local SGD, the full
paper-scale simulation loop, and the device-level round."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.configs.paper_hfl import MNIST_CONVEX
from repro.core.baselines import OraclePolicy
from repro.fed.client import local_sgd
from repro.fed.distributed import (make_hfl_round, make_train_step,
                                   stack_edge_params)
from repro.fed.edge import (broadcast_global, deadline_masked_aggregate,
                            effective_mask)
from repro.fed.hfl import HFLSimConfig, HFLSimulation
from repro.models import registry as R


def test_effective_mask_enough_arrivals():
    arrived = jnp.array([1.0, 0.0, 1.0, 1.0])
    tau = jnp.array([1.0, 9.0, 2.0, 3.0])
    w = effective_mask(arrived, tau, z_min=2)
    np.testing.assert_array_equal(np.asarray(w), [1, 0, 1, 1])


def test_effective_mask_z_fallback():
    """Fewer than Z arrivals -> wait for the Z fastest (Eq. 6 second case)."""
    arrived = jnp.array([0.0, 0.0, 1.0, 0.0])
    tau = jnp.array([5.0, 1.0, 2.0, 9.0])
    w = effective_mask(arrived, tau, z_min=2)
    np.testing.assert_array_equal(np.asarray(w), [0, 1, 1, 0])


def test_deadline_masked_aggregate_mean():
    edge = {"w": jnp.zeros((3,))}
    deltas = {"w": jnp.array([[3.0, 0, 0], [1.0, 0, 0], [8.0, 8, 8]])}
    arrived = jnp.array([1.0, 1.0, 0.0])
    tau = jnp.array([1.0, 1.0, 99.0])
    out, k = deadline_masked_aggregate(edge, deltas, arrived, tau, z_min=1)
    assert float(k) == 2
    np.testing.assert_allclose(np.asarray(out["w"]), [2.0, 0, 0])


def test_broadcast_global_means_edges():
    stacked = {"w": jnp.array([[2.0], [4.0]])}
    out = broadcast_global(stacked)
    np.testing.assert_allclose(np.asarray(out["w"]), [[3.0], [3.0]])


def test_local_sgd_matches_manual():
    params = {"w": jnp.array([1.0])}

    def loss(p, batch):
        return jnp.sum((p["w"] - batch["target"]) ** 2)

    batches = {"target": jnp.array([[2.0], [2.0]])}  # two steps
    delta, _ = local_sgd(params, loss, batches, lr=0.25)
    # step1: w=1 - 0.25*2*(1-2) = 1.5; step2: 1.5 - 0.25*2*(-0.5) = 1.75
    np.testing.assert_allclose(np.asarray(delta["w"]), [0.75])


def test_cifar_cnn_local_sgd_stays_finite():
    """Eq. 2 at the non-convex config's own lr, steps and batch on its
    non-IID synthetic shards: the paper CNN's local SGD stays finite
    (at lr 0.1 it overflowed within the first round, so every eval of a
    cifar10-nonconvex run read NaN)."""
    from repro.api import TrainSpec
    from repro.configs.paper_hfl import CIFAR10_NONCONVEX as exp
    from repro.data.federated import FederatedDataset
    from repro.models.logistic import make_loss_fn, make_model

    spec = TrainSpec(model="cnn")
    data = FederatedDataset.synthetic(exp.num_clients, kind="cifar", seed=0)
    params, _ = make_model("cnn", jax.random.PRNGKey(0),
                           input_shape=(32, 32, 3))
    loss = make_loss_fn("cnn")
    sgd = jax.jit(lambda p, b: local_sgd(p, loss, b, exp.lr))
    steps = exp.local_epochs * spec.batches_per_epoch
    for c in (0, 1, 3):   # shards that overflowed at lr 0.1
        cd = data.clients[c]
        idx = np.random.default_rng(c).integers(0, len(cd.y),
                                                (steps, spec.batch_size))
        delta, mean_loss = sgd(params, {"x": jnp.asarray(cd.x[idx]),
                                        "y": jnp.asarray(cd.y[idx])})
        assert np.isfinite(float(mean_loss)) and float(mean_loss) < 2.5
        assert all(np.isfinite(np.asarray(d)).all()
                   for d in jax.tree.leaves(delta))


def test_hfl_simulation_learns():
    import dataclasses as dc
    exp = dc.replace(MNIST_CONVEX, lr=0.05)
    cfg = HFLSimConfig(exp=exp, rounds=30, eval_every=30, seed=0)
    pol = OraclePolicy(exp.num_clients, exp.num_edge_servers, exp.budget)
    sim = HFLSimulation(cfg, pol)
    acc0 = sim.evaluate()
    hist = sim.run()
    assert hist.accuracy[-1] > max(acc0 + 0.2, 0.5)


def test_distributed_train_step_masking():
    """weights=0 must freeze params; weights=1 must change them."""
    cfg = get_config("qwen2-1.5b").reduced()
    key = jax.random.PRNGKey(0)
    params = R.init_params(cfg, key)
    batch = R.make_concrete_batch(cfg, InputShape("s", 16, 2, "train"), key)
    step = make_train_step(cfg, lr=0.1)
    p0, _ = step(params, batch, jnp.zeros((2,)))
    same = all(bool(jnp.allclose(a, b)) for a, b in
               zip(jax.tree.leaves(p0), jax.tree.leaves(params)))
    assert same, "zero participation must leave the edge model unchanged"
    p1, _ = step(params, batch, jnp.ones((2,)))
    changed = any(not bool(jnp.allclose(a, b)) for a, b in
                  zip(jax.tree.leaves(p1), jax.tree.leaves(params)))
    assert changed


def test_hfl_round_global_sync():
    """Edge models diverge between syncs and coincide on sync rounds."""
    cfg = get_config("qwen2-1.5b").reduced()
    key = jax.random.PRNGKey(0)
    params = R.init_params(cfg, key)
    n_edge = 2
    ep = stack_edge_params(params, n_edge)
    shape = InputShape("s", 16, 4, "train")
    batch = R.make_concrete_batch(cfg, shape, key)
    sb = jax.tree.map(lambda a: a.reshape((n_edge, 2) + a.shape[1:]), batch)
    # different data per edge
    w = jnp.ones((n_edge, 2))
    rnd = make_hfl_round(cfg, n_edge=n_edge, t_es=2, lr=0.1)
    ep1, _ = rnd(ep, sb, w, jnp.asarray(0))       # no sync after step 0
    e0 = jax.tree.leaves(ep1)[3]
    assert not bool(jnp.allclose(e0[0], e0[1])), "edges should diverge"
    ep2, _ = rnd(ep1, sb, w, jnp.asarray(1))      # (1+1) % 2 == 0 -> sync
    for leaf in jax.tree.leaves(ep2):
        np.testing.assert_allclose(np.asarray(leaf[0], np.float32),
                                   np.asarray(leaf[1], np.float32),
                                   rtol=2e-2, atol=2e-2)


def test_microbatch_equivalent_update():
    """Grad accumulation must match the single-shot step (same update)."""
    cfg = get_config("qwen2-1.5b").reduced()
    key = jax.random.PRNGKey(3)
    params = R.init_params(cfg, key)
    batch = R.make_concrete_batch(cfg, InputShape("s", 16, 4, "train"), key)
    w = jnp.ones((4,))
    p1, l1 = make_train_step(cfg, lr=0.05, microbatch=1)(params, batch, w)
    p2, l2 = make_train_step(cfg, lr=0.05, microbatch=2)(params, batch, w)
    np.testing.assert_allclose(float(l1), float(l2), rtol=5e-3)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-3, rtol=5e-2)
