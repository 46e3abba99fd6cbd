"""Faults planted in the program under test, to show the check sees them.

Each function patches the program in the current process before its
first compile; run each in a fresh process. ``bench/tests/test_faults.py``
drives a run on the CPU with each of them, and ``bench/readings.py``
reads each on the chip at the cell's own size.

* ``frozen``: local SGD returns zero deltas, so every edge model keeps
  its state;
* ``half_batch``: the local loss is the mean over the first half of each
  minibatch, the rest left out;
* ``altered``: the selection solver's answer loses its highest-index
  client in every round, where the answer is produced.

The exchange between chips is not a fault these one-chip cells can have.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def frozen():
    import repro.fed.batched as batched

    def zero_multi(params, loss_fn, batches, lr, per_client_params=False,
                   unroll=1):
        n = jax.tree.leaves(batches)[0].shape[0]
        return (jax.tree.map(jnp.zeros_like, params),
                jnp.zeros((n,), jnp.float32))

    def zero_one(params, loss_fn, batches, lr, unroll=1):
        return jax.tree.map(jnp.zeros_like, params), jnp.zeros((), jnp.float32)

    batched.local_sgd_multi = zero_multi
    batched.local_sgd = zero_one


def half_batch():
    import repro.experiment.sweep as sweep
    orig = sweep.make_loss_fn

    def make(kind):
        loss = orig(kind)

        def half(params, batch):
            b = batch["y"].shape[0] // 2
            return loss(params, {"x": batch["x"][:b], "y": batch["y"][:b]})
        return half

    sweep.make_loss_fn = make


def altered():
    import repro.policies.cocs as cocs

    def drop_last(solve):
        def wrapped(*args, **kw):
            a = solve(*args, **kw)
            last = jnp.max(jnp.where(a >= 0, jnp.arange(a.shape[0]), -1))
            return jnp.where(jnp.arange(a.shape[0]) == last, -1, a)
        return wrapped

    cocs.greedy_assign = drop_last(cocs.greedy_assign)
    cocs.flgreedy_assign = drop_last(cocs.flgreedy_assign)


FAULTS = {"frozen": frozen, "half_batch": half_batch, "altered": altered}
