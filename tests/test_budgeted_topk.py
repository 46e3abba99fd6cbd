"""budgeted_topk kernel package: bitwise equivalence to the legacy
while-loop solvers, oracle optimality bounds, and the bitonic sort core.

The greedy pick order is a strict total order (density desc, flat index
desc), so the tile-sorted walk must reproduce ``greedy_assign`` /
``flgreedy_assign`` *bitwise* — ties, zero budgets and all-infeasible
instances included. Property-style tests run under hypothesis."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.selection import (SelectionProblem, brute_force_select,
                                  check_feasible, selection_utility)
from repro.kernels.budgeted_topk import (bitonic_sort_desc, budgeted_topk,
                                         flgreedy_topk, sorted_candidates,
                                         sorted_candidates_ref)
from repro.policies.solvers import flgreedy_assign, greedy_assign


def random_instance(rng, n, m, budget=None, quantized=False):
    """values/costs/budgets/eligible arrays; ``quantized`` forces ties."""
    values = rng.uniform(0, 1, (n, m))
    if quantized:
        values = np.round(values * 4) / 4.0
    costs = rng.uniform(0.2, 1.0, n)
    if quantized:
        costs = np.round(costs * 4) / 4.0 + 0.25
    budgets = np.full(m, budget if budget is not None
                      else rng.uniform(0.5, 2.0))
    eligible = rng.uniform(size=(n, m)) < 0.7
    return (jnp.asarray(values, jnp.float32), jnp.asarray(costs, jnp.float32),
            jnp.asarray(budgets, jnp.float32), jnp.asarray(eligible))


def legacy_args(inst):
    v, c, b, e = inst
    return v, c, b, e


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 12),
       m=st.integers(1, 4), quantized=st.booleans())
def test_budgeted_topk_bitwise_vs_legacy(seed, n, m, quantized):
    rng = np.random.default_rng(seed)
    v, c, b, e = random_instance(rng, n, m, quantized=quantized)
    legacy = greedy_assign(v, c, b, e, use_kernel=False)
    walk = budgeted_topk(v, c, b, e, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(walk))


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 12),
       m=st.integers(1, 4), quantized=st.booleans())
def test_flgreedy_topk_bitwise_vs_legacy(seed, n, m, quantized):
    rng = np.random.default_rng(seed)
    v, c, b, e = random_instance(rng, n, m, quantized=quantized)
    legacy = flgreedy_assign(v, c, b, e, use_kernel=False)
    walk = flgreedy_topk(v, c, b, e)
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(walk))


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("n,m", [(7, 3), (13, 2), (5, 4)])
def test_interpret_kernel_bitwise_vs_legacy(seed, n, m):
    """The tile-local Pallas sort (interpret mode, tile smaller than N so
    the cross-tile merge actually runs) feeds the same walk decisions."""
    rng = np.random.default_rng(seed)
    v, c, b, e = random_instance(rng, n, m, quantized=(seed % 2 == 0))
    legacy = greedy_assign(v, c, b, e, use_kernel=False)
    kern = budgeted_topk(v, c, b, e, use_kernel=True, tile=4,
                         interpret=True)
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(kern))
    legacy_fl = flgreedy_assign(v, c, b, e, use_kernel=False)
    kern_fl = flgreedy_topk(v, c, b, e, use_kernel=True, tile=4,
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(legacy_fl), np.asarray(kern_fl))


@pytest.mark.parametrize("seed", range(6))
def test_solver_kernel_flag_routes_and_matches(seed):
    """greedy_assign(use_kernel=True) is the public TPU routing — on CPU
    it runs the interpret kernel and must still match the while-loop."""
    rng = np.random.default_rng(seed)
    v, c, b, e = random_instance(rng, 11, 3)
    np.testing.assert_array_equal(
        np.asarray(greedy_assign(v, c, b, e, use_kernel=False)),
        np.asarray(greedy_assign(v, c, b, e, use_kernel=True, tile=4,
                                 interpret=True)))
    np.testing.assert_array_equal(
        np.asarray(flgreedy_assign(v, c, b, e, use_kernel=False)),
        np.asarray(flgreedy_assign(v, c, b, e, use_kernel=True, tile=4,
                                   interpret=True)))


@pytest.mark.parametrize("seed", range(10))
def test_budgeted_topk_near_optimal_vs_brute_force(seed):
    """Same 1/2-approximation the legacy greedy carries (small N oracle)."""
    rng = np.random.default_rng(seed)
    v, c, b, e = random_instance(rng, 7, 2)
    prob = SelectionProblem(np.asarray(v, np.float64),
                            np.asarray(c, np.float64),
                            np.asarray(b, np.float64), np.asarray(e))
    assign = np.asarray(budgeted_topk(v, c, b, e), np.int64)
    assert check_feasible(prob, assign)
    _, opt = brute_force_select(prob)
    got = selection_utility(prob, assign)
    assert got >= 0.5 * opt - 1e-6, (got, opt)


@pytest.mark.parametrize("seed", range(10))
def test_flgreedy_topk_feasible_and_bounded(seed):
    rng = np.random.default_rng(seed)
    v, c, b, e = random_instance(rng, 7, 2)
    prob = SelectionProblem(np.asarray(v, np.float64),
                            np.asarray(c, np.float64),
                            np.asarray(b, np.float64), np.asarray(e))
    assign = np.asarray(flgreedy_topk(v, c, b, e), np.int64)
    assert check_feasible(prob, assign)
    _, opt = brute_force_select(prob, sqrt_utility=True)
    got = selection_utility(prob, assign, sqrt_utility=True)
    assert got >= opt / ((1 + 0.3) * (2 + 2 * prob.m)) - 1e-6


def test_zero_budget_selects_nobody():
    rng = np.random.default_rng(0)
    v, c, b, e = random_instance(rng, 9, 3, budget=0.0)
    for out in (budgeted_topk(v, c, b, e), flgreedy_topk(v, c, b, e),
                budgeted_topk(v, c, b, e, use_kernel=True, tile=4,
                              interpret=True)):
        assert (np.asarray(out) == -1).all()


def test_all_infeasible_selects_nobody():
    rng = np.random.default_rng(1)
    v, c, b, _ = random_instance(rng, 9, 3)
    e = jnp.zeros((9, 3), bool)
    for out in (budgeted_topk(v, c, b, e), flgreedy_topk(v, c, b, e),
                budgeted_topk(v, c, b, e, use_kernel=True, tile=4,
                              interpret=True)):
        assert (np.asarray(out) == -1).all()


def test_all_ties_matches_legacy():
    """Every density identical: the walk must fall back on the flat-index
    tie-break exactly as the legacy reversed argmax does."""
    n, m = 10, 3
    v = jnp.ones((n, m), jnp.float32)
    c = jnp.ones((n,), jnp.float32)
    b = jnp.full((m,), 2.5, jnp.float32)
    e = jnp.ones((n, m), bool)
    np.testing.assert_array_equal(
        np.asarray(greedy_assign(v, c, b, e, use_kernel=False)),
        np.asarray(budgeted_topk(v, c, b, e)))
    np.testing.assert_array_equal(
        np.asarray(greedy_assign(v, c, b, e, use_kernel=False)),
        np.asarray(budgeted_topk(v, c, b, e, use_kernel=True, tile=4,
                                 interpret=True)))


@pytest.mark.parametrize("seed", range(6))
def test_sorted_candidates_kernel_matches_ref(seed):
    rng = np.random.default_rng(seed)
    v, c, b, e = random_instance(rng, 13, 3, quantized=(seed % 2 == 0))
    d_ref, i_ref = sorted_candidates_ref(v, c, e)
    d_k, i_k = sorted_candidates(v, c, e, use_kernel=True, tile=4,
                                 interpret=True)
    # per-tile segments each sorted desc with the composite tie-break
    d_k, i_k = np.asarray(d_k), np.asarray(i_k)
    for seg in range(d_k.shape[0]):
        ds, is_ = d_k[seg], i_k[seg]
        for a in range(len(ds) - 1):
            assert (ds[a] > ds[a + 1]
                    or (ds[a] == ds[a + 1] and is_[a] >= is_[a + 1]))
    # the union of real entries is the ref candidate multiset; pads are
    # idx -1 (p2 fill) or idx >= N*M (row padding), all density -inf
    flat_i, flat_d = i_k.reshape(-1), d_k.reshape(-1)
    mask = (flat_i >= 0) & (flat_i < int(np.asarray(v).size))
    assert (flat_d[~mask] == -np.inf).all()
    got = sorted(zip(flat_i[mask].tolist(), flat_d[mask].tolist()))
    want = sorted(zip(np.asarray(i_ref)[0].tolist(),
                      np.asarray(d_ref)[0].tolist()))
    assert got == want


def test_bitonic_sort_desc_matches_lexsort():
    rng = np.random.default_rng(7)
    for _ in range(10):
        p = 32
        d = rng.uniform(0, 1, p).astype(np.float32)
        d[rng.uniform(size=p) < 0.2] = -np.inf
        d = np.round(d * 8) / 8.0          # force ties
        ix = rng.permutation(p).astype(np.int32)
        ds, ixs = bitonic_sort_desc(jnp.asarray(d).reshape(1, p),
                                    jnp.asarray(ix).reshape(1, p))
        order = np.lexsort((-ix, -d))       # density desc, idx desc
        np.testing.assert_array_equal(np.asarray(ds)[0], d[order])
        np.testing.assert_array_equal(np.asarray(ixs)[0], ix[order])
