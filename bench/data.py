"""The cell's federated dataset, made from the seed.

Class-conditional Gaussian blobs at the configuration's input shape
(unit noise around a class mean of norm ``class_sep``), split non-IID as
in the paper: the pool is sorted by label, cut into ``labels_per_client``
shards per client and dealt at random, so each client holds about
``labels_per_client`` classes. Labels and the split are drawn on the
host; the features are drawn on the device in one call.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def make(cfg: dict, seed: int) -> Dict[str, np.ndarray]:
    """Host arrays: ``x`` (N, L, ...) zero-padded past ``sizes``,
    ``y`` (N, L), ``sizes`` (N,), ``test_x``, ``test_y``."""
    d = cfg["data"]
    shape = tuple(d["shape"])
    dim = int(np.prod(shape))
    n = int(cfg["world"]["num_clients"])
    per, n_test = int(d["samples_per_client"]), int(d["test_samples"])
    classes, lpc = int(d["classes"]), int(d["labels_per_client"])
    rng = np.random.default_rng(seed)
    mus = rng.standard_normal((classes, dim))
    mus *= d["class_sep"] / np.linalg.norm(mus, axis=1, keepdims=True)
    y_pool = rng.integers(0, classes, n * per)
    y_test = rng.integers(0, classes, n_test)
    shards = np.array_split(np.argsort(y_pool, kind="stable"), n * lpc)
    deal = rng.permutation(n * lpc)
    idx = []
    for c in range(n):
        own = np.concatenate([shards[s] for s in deal[c * lpc:(c + 1) * lpc]])
        rng.shuffle(own)
        idx.append(own)
    sizes = np.array([len(i) for i in idx], np.int32)
    lmax = int(sizes.max())
    gather = np.zeros((n, lmax), np.int64)
    mask = np.zeros((n, lmax), bool)
    for c, own in enumerate(idx):
        gather[c, :len(own)] = own
        mask[c, :len(own)] = True
    y_all = np.concatenate([y_pool, y_test]).astype(np.int32)

    @jax.jit
    def features(key, means, labels, gather, mask):
        noise = jax.random.normal(key, (labels.shape[0], dim), jnp.float32)
        pool = noise + means[labels]
        train = jnp.where(mask[..., None], pool[gather], 0.0)
        return train, pool[n * per:]

    key = jax.random.PRNGKey(int(seed) % (2 ** 32))
    train, test = features(key, jnp.asarray(mus, jnp.float32),
                           jnp.asarray(y_all), jnp.asarray(gather),
                           jnp.asarray(mask))
    y = np.where(mask, y_pool[gather], 0).astype(np.int32)
    return {"x": np.asarray(train).reshape((n, lmax) + shape),
            "y": y, "sizes": sizes,
            "test_x": np.asarray(test).reshape((n_test,) + shape),
            "test_y": y_test.astype(np.int32)}


def federated(arrays: Dict[str, np.ndarray]):
    """The program's ``FederatedDataset`` over these arrays."""
    from repro.data.federated import ClientData, FederatedDataset
    clients = [ClientData(arrays["x"][c, :s], arrays["y"][c, :s])
               for c, s in enumerate(arrays["sizes"])]
    return FederatedDataset(clients=clients, test_x=arrays["test_x"],
                            test_y=arrays["test_y"])
