#!/usr/bin/env python3
"""Smoke run of the fused HFL engine on a TPU, through ``repro.run``.

Every phase runs tier 4 — the device-resident environment with COCS
selection, Eq. 2 local SGD and Eq. 3 aggregation inside one compiled
scan per eval interval — with the Pallas kernels compiled
(``interpret=False``):

  (a) the paper's non-convex setting at its published widths:
      ``cifar10-nonconvex`` (N=50 clients, M=3 edge servers, P3 sqrt
      utility -> ``flgreedy_topk``) training the 2x [5x5 conv(64) +
      maxpool], FC 384, FC 192 CNN on 32x32x3 synthetic data made from
      the seed;
  (b) the ``metropolis-1k`` cohort: N=1000, M=12, logreg, P2
      (``budgeted_topk``);
  (c) (a) and (b) again with every kernel off (``use_kernel=False`` on
      the env, train and policy specs); accuracy must agree to
      ``ACC_ATOL``. The selection kernel is held bitwise to the jnp
      greedy walk on the same chip-realized rounds, so a selection
      difference between the two runs can only come from the env
      tables. The four ``context_pairwise`` tables and one
      ``masked_aggregate`` call are compared kernel vs jnp. Where the
      chip breaks the bitwise contract (tables, selections or
      participants), the first differing round is printed and the
      kernels-on rounds are held to the host float64 oracle: each
      metric within the tolerance of
      ``tests/test_kernel_context_parity.py`` of the error the
      kernels-off (XLA) rounds show on the same chip. The chip's own
      float32 ``log`` misses float64 by more than those tolerances
      (TPU v5 lite: ~3.7e-4 relative), and both paths share it.

``--four-chips`` runs only the sharded phase instead: ``metropolis-100k``
with ``ShardSpec(clients=4)`` against the dense tier-4 run of the same
spec on one of those chips; selections must be bitwise equal.

Timings are one run's wall clock, printed for information — not a
benchmark. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
``count`` is the number of chips the phase ran on. With no TPU visible,
when any phase raises or any parity check fails,
the script exits non-zero and prints no such line.

    python chip_smoke.py
    python chip_smoke.py --four-chips
"""
from __future__ import annotations

import argparse
import dataclasses as dc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# the same selections under a different Eq. 3 aggregation arithmetic
# agree on accuracy to this bound (tests/test_fed_batched.py, per-client
# loop vs batched engine); one test sample of 2000 is 5e-4
ACC_ATOL = 1e-3
# device float32 vs host float64 rounds (tests/test_kernel_context_parity.py)
COST_RTOL, CTX_ATOL, LAT_RTOL, ELIG_MISMATCH = 1e-5, 2e-5, 2e-4, 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def kernels_off(spec):
    """The same experiment with every Pallas kernel routed off."""
    return dc.replace(
        spec,
        env=dc.replace(spec.env, use_kernel=False),
        train=dc.replace(spec.train, use_kernel=False),
        policy=dc.replace(spec.policy, options=spec.policy.options
                          + (("use_kernel", False),)))


def timed_run(tag: str, spec, repeat: bool = True):
    """Run ``spec``; with ``repeat`` run it again: the first call
    compiles, the second is warm and must reproduce the first bitwise."""
    import repro
    t0 = time.perf_counter()
    res = repro.run(spec)
    first = time.perf_counter() - t0
    if res.tier != 4:
        raise RuntimeError(f"{tag}: ran at tier {res.tier}, expected 4")
    timing = f"first_call_s={first:.3f}"
    if repeat:
        t0 = time.perf_counter()
        again = repro.run(spec)
        warm = time.perf_counter() - t0
        if not np.array_equal(res.selections, again.selections):
            raise RuntimeError(f"{tag}: a repeated run changed its "
                               "selections")
        timing += f" warm_run_s={warm:.3f} compile_s~{first - warm:.3f}"
    acc, loss = np.asarray(res.accuracy), np.asarray(res.loss)
    parts = np.asarray(res.participants)
    log(f"[{tag}] tier={res.tier} {timing}")
    log(f"[{tag}] participants/round seed0: "
        + " ".join(f"{p:g}" for p in parts[0])
        + f" (mean {parts.mean():.2f})")
    log(f"[{tag}] accuracy at rounds "
        f"{[int(r) for r in np.asarray(res.eval_rounds)]}: "
        + " ".join(f"{a:.4f}" for a in acc[0])
        + " loss " + " ".join(f"{v:.4f}" for v in loss[0]))
    if not (np.all(np.isfinite(acc)) and np.all(np.isfinite(loss))):
        raise RuntimeError(f"{tag}: non-finite accuracy {acc} / loss {loss}")
    return res


def context_tables(env_spec, seed: int = 0):
    """Max relative difference of the four ``context_pairwise`` tables,
    compiled kernel vs jnp oracle, on inputs drawn at the preset's own
    (N, M) and physics."""
    import jax
    import jax.numpy as jnp

    from repro.api import build_env
    from repro.kernels.context_pairwise import pairwise_context

    sp = build_env(env_spec).spec
    n, m = sp.num_clients, sp.num_edge_servers
    rng = np.random.default_rng(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)         # noqa: E731
    args = (f32(rng.uniform(-sp.area, sp.area, (n, 2))),
            f32(rng.uniform(-sp.area, sp.area, (m, 2))),
            f32(rng.uniform(sp.bandwidth_low, sp.bandwidth_high, n)),
            f32(rng.uniform(sp.compute_low, sp.compute_high, n)),
            f32(rng.exponential(1.0, (n, m))),
            f32(rng.exponential(1.0, (n, m))))
    phys = dict(tx_w=sp.tx_w, noise_psd_w=sp.noise_psd_w,
                update_bits=sp.update_bits, workload=sp.workload)
    ref = jax.jit(lambda *a: pairwise_context(*a, use_kernel=False,
                                              **phys))(*args)
    kern = pairwise_context(*args, use_kernel=True, **phys)
    out = {}
    for name in ref._fields:
        r = np.asarray(getattr(ref, name), np.float64)
        k = np.asarray(getattr(kern, name), np.float64)
        out[name] = float(np.max(np.abs(k - r) / np.maximum(np.abs(r),
                                                             1e-30)))
    return out


def aggregate_diff(spec, slots: int = 8, seed: int = 0) -> float:
    """Largest difference of one Eq. 3 aggregation at the model's
    parameter count, compiled ``masked_aggregate`` kernel vs jnp,
    relative to the largest output."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.masked_aggregate import masked_aggregate_flat
    from repro.models.logistic import make_model

    shape = (32, 32, 3) if spec.train.model == "cnn" else (784,)
    params, _ = make_model(spec.train.model, jax.random.PRNGKey(seed),
                           input_shape=shape)
    d = sum(int(p.size) for p in jax.tree.leaves(params))
    rng = np.random.default_rng(seed)
    param = jnp.asarray(rng.normal(size=d), jnp.float32)
    deltas = jnp.asarray(rng.normal(scale=1e-2, size=(slots, d)),
                         jnp.float32)
    w = jnp.asarray(rng.uniform(size=slots) < 0.7, jnp.float32)
    ref = np.asarray(jax.jit(masked_aggregate_flat)(param, deltas, w),
                     np.float64)
    kern = np.asarray(masked_aggregate_flat(param, deltas, w,
                                            use_kernel=True), np.float64)
    return float(np.max(np.abs(kern - ref)) / np.max(np.abs(ref)))


def oracle_errors(env_spec, kernels: bool, rounds: int = 2) -> dict:
    """Device rounds (kernels on or off) against the host float64
    oracle: the quantities tests/test_kernel_context_parity.py bounds."""
    from repro.api import build_env

    env = build_env(dc.replace(env_spec, use_kernel=kernels))
    hb = env.host_env().rollout_multi([0], rounds)
    db = env.rollout_multi([0], rounds)
    db = type(db)(*(np.asarray(x) for x in db))
    deadline = env.cfg.deadline_s
    boundary = np.abs(hb.latency - deadline) < 1e-4 * deadline
    return {
        "eligible": float(np.mean(np.asarray(hb.eligible) != db.eligible)),
        "cost": float(np.max(np.abs(hb.costs - db.costs)
                             / np.maximum(np.abs(hb.costs), 1e-30))),
        "context": float(np.max(np.abs(hb.contexts - db.contexts))),
        "latency": float(np.max(np.abs(hb.latency - db.latency)
                                / np.maximum(np.abs(hb.latency), 1e-30))),
        "outcomes": float(np.mean((hb.outcomes != db.outcomes)
                                  & ~boundary)),
    }


def oracle_check(tag: str, env_spec) -> bool:
    """The kernels-on rounds within the CPU tests' tolerances of the
    kernels-off rounds' own distance from the float64 oracle."""
    tol = {"eligible": ELIG_MISMATCH, "cost": COST_RTOL,
           "context": CTX_ATOL, "latency": LAT_RTOL, "outcomes": 0.0}
    on = oracle_errors(env_spec, kernels=True)
    off = oracle_errors(env_spec, kernels=False)
    ok = all(on[k] <= off[k] + tol[k] for k in tol)
    log(f"[{tag}] host float64 oracle (2 rounds), kernels on / off: "
        + " ".join(f"{k} {on[k]:.2e}/{off[k]:.2e}" for k in tol)
        + f" -> {'pass' if ok else 'FAIL'}")
    return ok


def selection_check(tag: str, spec, rounds: int = 3) -> bool:
    """The selection solver, kernel vs jnp greedy walk, on identical
    inputs: the kernels-on env's rounds realized on the chip, valued by
    ``true_p`` (the Oracle's values) and by a coarsened copy that forces
    ties. The walk's sort only compares density keys, which XLA computes
    the same way on both paths, so the assignments must be bitwise
    equal."""
    import jax.numpy as jnp

    from repro.api import build_env
    from repro.policies.solvers import flgreedy_assign, greedy_assign

    env = build_env(dc.replace(spec.env, use_kernel=True))
    cfg = env.cfg
    solve = flgreedy_assign if cfg.utility == "sqrt" else greedy_assign
    budgets = jnp.full(cfg.num_edge_servers, cfg.budget, jnp.float32)
    batch = env.rollout_multi(list(spec.seeds), rounds)
    differ = checked = 0
    for s in range(len(spec.seeds)):
        for t in range(rounds):
            costs = jnp.asarray(batch.costs[s, t], jnp.float32)
            elig = jnp.asarray(batch.eligible[s, t], bool)
            p = jnp.asarray(batch.true_p[s, t], jnp.float32)
            for values in (p, jnp.round(p * 8.0) / 8.0):
                k = solve(values, costs, budgets, elig, use_kernel=True)
                r = solve(values, costs, budgets, elig, use_kernel=False)
                checked += 1
                differ += not np.array_equal(np.asarray(k), np.asarray(r))
    log(f"[{tag}] {solve.__name__} kernel vs jnp on {checked} chip-realized "
        f"rounds: {differ} differ -> {'pass' if not differ else 'FAIL'}")
    return differ == 0


def parity(tag: str, spec, on) -> bool:
    """Phase (c) for one spec: kernels off vs the kernels-on result."""
    off = timed_run(f"{tag}/kernels-off", kernels_off(spec))
    tables = context_tables(spec.env)
    log(f"[{tag}] context_pairwise kernel vs jnp, max rel diff: "
        + " ".join(f"{k}={v:.3e}" for k, v in tables.items()))
    tables_bitwise = not any(tables.values())
    log(f"[{tag}] masked_aggregate kernel vs jnp, max diff / max out: "
        f"{aggregate_diff(spec):.3e}")
    sel_equal = np.array_equal(on.selections, off.selections)
    parts_equal = np.array_equal(on.participants, off.participants)
    acc_diff = float(np.max(np.abs(np.asarray(on.accuracy)
                                   - np.asarray(off.accuracy))))
    log(f"[{tag}] parity: selections bitwise equal={sel_equal} "
        f"participants equal={parts_equal} "
        f"max |accuracy diff|={acc_diff:.3e} (bound {ACC_ATOL:g})")
    ok = selection_check(tag, spec) and acc_diff <= ACC_ATOL
    if not (sel_equal and parts_equal):
        differ = ((on.selections != off.selections).any(axis=(0, 2))
                  | (on.participants != off.participants).any(axis=0))
        rounds = np.nonzero(differ)[0]
        log(f"[{tag}] parity: first differing round {int(rounds[0]) + 1} "
            f"of {len(differ)} ({len(rounds)} rounds differ)")
    if not (tables_bitwise and sel_equal and parts_equal):
        # the bitwise contract is broken on this chip: the kernel answers
        # to the float64 oracle
        ok = oracle_check(tag, spec.env) and ok
    log(f"[{tag}] parity check: {'pass' if ok else 'FAIL'}")
    return ok


def specs():
    """The smoke's experiments: phase (a), phase (b), the four-chip one."""
    from repro import api

    paper = api.ExperimentSpec(
        policy=api.PolicySpec("cocs"),
        env=api.EnvSpec("paper", backend="device",
                        config="cifar10-nonconvex"),
        train=api.TrainSpec(model="cnn"),
        eval=api.EvalSpec(eval_every=5), horizon=10, seeds=(0,))
    cohort = api.ExperimentSpec(
        policy=api.PolicySpec("cocs"),
        env=api.EnvSpec("metropolis-1k"),
        train=api.TrainSpec(),
        eval=api.EvalSpec(eval_every=3), horizon=6, seeds=(0,))
    metropolis = api.ExperimentSpec(
        policy=api.PolicySpec("cocs"),
        env=api.EnvSpec("metropolis-100k", true_p="analytic"),
        train=api.TrainSpec(batch_size=16),
        eval=api.EvalSpec(eval_every=2), horizon=4, seeds=(0,),
        shard=api.ShardSpec(clients=4))
    return paper, cohort, metropolis


def one_chip_phases(paper, cohort) -> bool:
    a = timed_run("a: cifar10-nonconvex cnn N=50 M=3", paper)
    b = timed_run("b: metropolis-1k logreg N=1000 M=12", cohort)
    ok_a = parity("c: paper", paper, a)
    ok_b = parity("c: metropolis-1k", cohort, b)
    return ok_a and ok_b


def four_chip_phase(sharded) -> bool:
    """``sharded`` (a spec with a ShardSpec) vs the same spec dense."""
    import jax

    need = sharded.shard.clients * sharded.shard.seeds
    if len(jax.devices()) < need:
        raise RuntimeError(f"the sharded phase needs {need} devices, "
                           f"found {len(jax.devices())}")
    tag = sharded.env.scenario
    # one call each: four chips cost four times a repeat's seconds
    res = timed_run(f"{tag} sharded clients={sharded.shard.clients}",
                    sharded, repeat=False)
    dense = timed_run(f"{tag} dense one chip",
                      dc.replace(sharded, shard=None), repeat=False)
    sel_equal = np.array_equal(res.selections, dense.selections)
    parts_equal = np.array_equal(res.participants, dense.participants)
    acc_diff = float(np.max(np.abs(np.asarray(res.accuracy)
                                   - np.asarray(dense.accuracy))))
    ok = sel_equal and parts_equal and acc_diff <= ACC_ATOL
    log(f"[{tag}] sharded vs dense: selections bitwise equal={sel_equal} "
        f"participants equal={parts_equal} "
        f"max |accuracy diff|={acc_diff:.3e} -> "
        f"{'pass' if ok else 'FAIL'}")
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the metropolis-100k sharded-vs-dense "
                         "phase on four chips")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    import jax

    from repro.kernels.common import resolve_kernel_mode

    paper, cohort, metropolis = specs()
    devs = jax.devices()
    # the devices the phase runs on: one, or the sharded phase's mesh
    used = (metropolis.shard.clients * metropolis.shard.seeds
            if args.four_chips else 1)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": used}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU visible (JAX sees {devs[0].platform}); "
              "nothing to smoke-test", file=sys.stderr)
        return 2
    mode = resolve_kernel_mode(None)
    log(f"device: {device} kernel mode (use_kernel, interpret)={mode} "
        f"compile cache: {cache}")
    if mode != (True, False):
        raise RuntimeError(f"kernels resolve to {mode} on a TPU")
    ok = (four_chip_phase(metropolis) if args.four_chips
          else one_chip_phases(paper, cohort))
    if not ok:
        print("chip_smoke: a parity check failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
