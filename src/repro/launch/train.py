"""Training launcher.

Two modes:
  * ``--paper``: the paper's HFL experiment (N=50 clients, M=3 ESs, COCS
    in the loop) on CPU — real training, real selection, real deadlines.
  * ``--arch <id>``: LM-scale HFL training of an assigned architecture's
    REDUCED variant on the local device(s): client cohorts = token shards,
    COCS decides which cohorts' deltas enter each edge aggregation.

The full-size configs are exercised via ``repro.launch.dryrun`` (this
container has one CPU device; the production mesh is compile-only).
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import envs, policies
from repro.compile_cache import enable_compile_cache
from repro.configs import ARCH_IDS, get_config
from repro.configs.paper_hfl import CIFAR10_NONCONVEX, MNIST_CONVEX
from repro.data.tokens import client_token_shards
from repro.fed.distributed import make_train_step
from repro.models import registry as R


def run_paper(args) -> int:
    from repro import api
    from repro.data.federated import FederatedDataset
    exp = CIFAR10_NONCONVEX if args.nonconvex else MNIST_CONVEX
    spec = api.ExperimentSpec(
        policy=api.PolicySpec("cocs", options=(("h_t", exp.h_t),)),
        env=api.env_spec_from_config(exp),
        train=api.TrainSpec(model="cnn" if args.nonconvex else "logreg"),
        eval=api.EvalSpec(args.eval_every),
        horizon=args.rounds, seeds=(args.seed,))
    # seed-keyed synthetic data, matching the historical HFLSimulation
    # default (the sweep engine's own fallback is seed=0 shared data)
    data = FederatedDataset.synthetic(
        exp.num_clients, kind="cifar" if args.nonconvex else "mnist",
        seed=args.seed)
    res = api.run(spec, data=data)   # tier 3: fused policy+training+eval
    for r, a in zip(res.eval_rounds, res.accuracy[0]):
        print(f"round {int(r):4d}  test_acc {a:.4f}", flush=True)
    print(f"final accuracy: {res.accuracy[0][-1]:.4f}")
    return 0


def run_lm(args) -> int:
    cfg = get_config(args.arch).reduced()
    n_clients = args.clients
    horizon = args.rounds
    exp = MNIST_CONVEX
    import dataclasses as dc
    exp_n = dc.replace(exp, num_clients=n_clients)
    spec = policies.PolicySpec.from_experiment(exp_n, horizon)
    policy = policies.make_legacy("cocs", spec, seed=args.seed, h_t=exp.h_t)
    sim = envs.make(args.scenario, exp_n).make_sim(args.seed)
    shards = client_token_shards(n_clients, cfg.vocab_size, args.seq_len,
                                 args.batch, seed=args.seed)
    rngs = [np.random.default_rng(args.seed + c) for c in range(n_clients)]
    params = R.init_params(cfg, jax.random.PRNGKey(args.seed))
    step = jax.jit(make_train_step(cfg, lr=args.lr))
    t0 = time.time()
    for t in range(horizon):
        rd = sim.round(t)
        assign = policy.select(rd)
        policy.update(rd, assign)
        sel = np.nonzero(assign >= 0)[0]
        losses = []
        for c in sel:
            batch = shards[c].sample(rngs[c])
            w = jnp.full((args.batch,), float(rd.outcomes[c, assign[c]]))
            params, loss = step(params, jax.tree.map(jnp.asarray, batch), w)
            losses.append(float(loss))
        if (t + 1) % 10 == 0 or t == 0:
            print(f"round {t+1:4d}  clients {len(sel):2d}  "
                  f"mean_loss {np.mean(losses) if losses else float('nan'):.4f}  "
                  f"({time.time()-t0:.0f}s)", flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true")
    ap.add_argument("--nonconvex", action="store_true")
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--scenario", default="paper",
                    choices=sorted(envs.SCENARIOS))
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.paper:
        return run_paper(args)
    if args.arch:
        return run_lm(args)
    ap.error("choose --paper or --arch <id>")
    return 2


if __name__ == "__main__":
    sys.exit(main())
