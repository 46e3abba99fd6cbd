"""A 100,000-client metropolis on a client-sharded device mesh.

The dense tier-4 engine caps near 1k clients: the (N, M) rate/latency
tables and the N-wide greedy solver live on one device. ``ShardSpec``
lifts the client axis onto a ``("clients",)`` mesh axis (``repro.mesh``)
— statics, mobility, draws, CC-MAB state and the candidate tables all
run as (N/shards, M) shards, and budgeted selection merges per-shard
heads with an ``all_gather`` champion reduce that is bitwise the dense
walk. The client axis is split over every visible device: the four
chips of a TPU v5e host, or — with no accelerator — 8 host devices the
script carves out of the CPU (the flag must be set before jax is
imported, and only the CPU backend reads it).

    PYTHONPATH=src python examples/sharded_cohort.py
"""
import os

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax                                                   # noqa: E402
import numpy as np                                           # noqa: E402

import repro                                                 # noqa: E402
from repro import api                                        # noqa: E402


def main():
    shards = len(jax.devices())
    spec = api.ExperimentSpec(
        policy=api.PolicySpec("cocs"),
        env=api.EnvSpec("metropolis-100k", true_p="analytic"),
        train=api.TrainSpec(batch_size=16),
        eval=api.EvalSpec(eval_every=4),
        horizon=8, seeds=(0,),
        shard=api.ShardSpec(clients=shards),  # one client shard per device
        obs=repro.obs.ObsSpec(telemetry=True))
    n = 100_000
    print(f"metropolis-100k: N={n} clients over a {shards}-way client mesh "
          f"({n // shards:,} clients/device on "
          f"{jax.devices()[0].platform}), duty-cycled arrivals")
    print("round-trip spec:",
          api.ExperimentSpec.from_json(spec.to_json()) == spec)

    res = repro.run(spec)
    assert res.tier == 4 and res.selections.shape == (1, 8, n)

    parts = np.asarray(res.participants)[0]
    print(f"participants/round: {parts.mean():.0f} "
          f"(min {parts.min():.0f}, max {parts.max():.0f})")
    print(f"final accuracy: {float(res.final_accuracy()[0]):.3f}")

    # on-device telemetry: per-round budget utilization of the
    # hierarchical cross-shard selection (1.0 = every edge-server
    # budget fully committed)
    util = np.asarray(res.telemetry["series"]["budget_util"])[0]
    print("budget utilization by round:",
          " ".join(f"{u:.3f}" for u in util))
    miss = np.asarray(res.telemetry["series"]["deadline_miss"])[0]
    print(f"deadline misses/round: mean {miss.mean():.0f}")


if __name__ == "__main__":
    main()
