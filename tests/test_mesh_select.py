"""Hierarchical sharded selection: bitwise equivalence to the dense
greedy solvers at any shard count.

The mesh engine's cross-shard merge walk (``repro.mesh.select``) claims
the shard topology is invisible: per-shard head scans + the champion
``all_gather`` merge pick the exact candidate sequence of the dense
``greedy_assign``/``flgreedy_assign`` walk — ties, zero budgets and
all-infeasible ES columns included. These tests pin that contract via
the single-device emulation (``hier_*_assign``), which runs the same
reduction tree without needing a multi-device runtime, plus the
counter-based draw slicing and the ``ShardSpec`` JSON round-trip the
sharded runner rests on. The live multi-device path is covered by
``tests/test_mesh_engine.py`` under a forced host mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import api
from repro.mesh import hier_flgreedy_assign, hier_greedy_assign
from repro.policies.solvers import flgreedy_assign, greedy_assign

SHARD_COUNTS = (1, 2, 4, 8)


def random_instance(rng, n, m, budget=None, quantized=False):
    values = rng.uniform(0, 1, (n, m))
    if quantized:
        values = np.round(values * 4) / 4.0
    costs = rng.uniform(0.2, 1.0, n)
    if quantized:
        costs = np.round(costs * 4) / 4.0 + 0.25
    budgets = np.full(m, budget if budget is not None
                      else rng.uniform(0.5, 2.0))
    eligible = rng.uniform(size=(n, m)) < 0.7
    return (jnp.asarray(values, jnp.float32),
            jnp.asarray(costs, jnp.float32),
            jnp.asarray(budgets, jnp.float32), jnp.asarray(eligible))


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 24),
       m=st.integers(1, 4), shards=st.sampled_from(SHARD_COUNTS),
       quantized=st.booleans())
def test_hier_greedy_bitwise_vs_dense(seed, n, m, shards, quantized):
    rng = np.random.default_rng(seed)
    v, c, b, e = random_instance(rng, n, m, quantized=quantized)
    dense = greedy_assign(v, c, b, e, use_kernel=False)
    hier = hier_greedy_assign(v, c, b, e, num_shards=shards)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(hier))


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 24),
       m=st.integers(1, 4), shards=st.sampled_from(SHARD_COUNTS),
       quantized=st.booleans())
def test_hier_flgreedy_bitwise_vs_dense(seed, n, m, shards, quantized):
    rng = np.random.default_rng(seed)
    v, c, b, e = random_instance(rng, n, m, quantized=quantized)
    dense = flgreedy_assign(v, c, b, e, use_kernel=False)
    hier = hier_flgreedy_assign(v, c, b, e, num_shards=shards, num_es=m)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(hier))


@pytest.mark.parametrize("shards", SHARD_COUNTS)
def test_hier_greedy_bitwise_at_1k(shards):
    """The acceptance-scale pin: N = 1000 (non-divisible counts pad)."""
    rng = np.random.default_rng(7)
    v, c, b, e = random_instance(rng, 1000, 8, budget=6.0)
    dense = greedy_assign(v, c, b, e, use_kernel=False)
    hier = hier_greedy_assign(v, c, b, e, num_shards=shards)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(hier))
    fl_dense = flgreedy_assign(v, c, b, e, use_kernel=False)
    fl_hier = hier_flgreedy_assign(v, c, b, e, num_shards=shards, num_es=8)
    np.testing.assert_array_equal(np.asarray(fl_dense), np.asarray(fl_hier))


@pytest.mark.parametrize("shards", (1, 4))
def test_hier_zero_budget_and_infeasible_es(shards):
    """Zero budgets select nobody; an all-infeasible ES gets no one even
    when other columns still admit clients."""
    rng = np.random.default_rng(3)
    v, c, _, e = random_instance(rng, 32, 4)
    zero = hier_greedy_assign(v, c, jnp.zeros(4), e, num_shards=shards)
    assert int(jnp.sum(zero >= 0)) == 0
    e_dead = e.at[:, 2].set(False)
    b = jnp.full(4, 2.0)
    dense = greedy_assign(v, c, b, e_dead, use_kernel=False)
    hier = hier_greedy_assign(v, c, b, e_dead, num_shards=shards)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(hier))
    assert int(jnp.sum(hier == 2)) == 0


def test_hier_all_ties():
    """Constant densities: pure tie-breaking order must still match."""
    n, m = 16, 3
    v = jnp.ones((n, m), jnp.float32) * 0.5
    c = jnp.ones(n, jnp.float32) * 0.5
    b = jnp.full(m, 1.5, jnp.float32)
    e = jnp.ones((n, m), bool)
    dense = greedy_assign(v, c, b, e, use_kernel=False)
    for shards in SHARD_COUNTS:
        hier = hier_greedy_assign(v, c, b, e, num_shards=shards)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(hier))


# -- counter-based draw slicing ----------------------------------------------


def test_shard_round_draws_slice_dense_stream():
    """Per-shard draw generation is a bitwise row slice of the dense
    ``jax.random`` streams (``round_draws``, ``fault_draws``) — the
    property that makes sharded env generation exact."""
    from repro.sim import draws
    n, m, k_mc = 64, 4, 8
    seed = jnp.uint32(5)
    for t in (0, 7):
        dense = draws.round_draws(seed, t, n, m, k_mc)
        for shards in (1, 2, 4):
            n_local = n // shards
            for s in range(shards):
                part = draws.shard_round_draws(seed, t, n, m, k_mc,
                                               s * n_local, n_local)
                lo = s * n_local
                for field in part._fields:
                    a = np.asarray(getattr(part, field))
                    b = np.asarray(getattr(dense, field))
                    # mc_* draws carry the client axis second: (K, N, M)
                    want = (b[:, lo:lo + n_local]
                            if field.startswith("mc_")
                            else b[lo:lo + n_local])
                    np.testing.assert_array_equal(a, want, err_msg=field)
                fpart = draws.shard_fault_draws(seed, t, n, m, lo, n_local)
                fdense = draws.fault_draws(seed, t, n, m)
                for field in fpart._fields:
                    b = np.asarray(getattr(fdense, field))
                    # out_u is the (M,) ES stream, replicated per shard
                    want = b if field == "out_u" else b[lo:lo + n_local]
                    np.testing.assert_array_equal(
                        np.asarray(getattr(fpart, field)), want,
                        err_msg=field)


# -- ShardSpec serialization -------------------------------------------------


def test_shard_spec_json_round_trip():
    spec = api.ExperimentSpec(
        policy=api.PolicySpec("cocs"),
        env=api.EnvSpec("metropolis-1k", true_p="analytic"),
        train=api.TrainSpec(), horizon=8, seeds=(0, 1),
        shard=api.ShardSpec(clients=4, seeds=2))
    back = api.ExperimentSpec.from_json(spec.to_json())
    assert back == spec
    assert back.shard == api.ShardSpec(clients=4, seeds=2)


def test_shard_spec_rejects_bad_axes():
    with pytest.raises(ValueError, match=">= 1"):
        api.ShardSpec(clients=0)
    with pytest.raises(ValueError, match="divide"):
        api.ExperimentSpec(seeds=(0, 1, 2), shard=api.ShardSpec(seeds=2))
