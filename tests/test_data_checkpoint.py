"""Data pipeline (non-IID invariants, hypothesis) + checkpoint roundtrip."""
import tempfile

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.checkpoint import latest_checkpoint, restore_pytree, save_pytree
from repro.data.federated import FederatedDataset
from repro.data.synthetic import make_synthetic_classification, non_iid_split
from repro.data.tokens import client_token_shards


@settings(max_examples=20, deadline=None)
@given(num_clients=st.integers(10, 30), lpc=st.integers(1, 3),
       seed=st.integers(0, 100))
def test_non_iid_split_label_budget(num_clients, lpc, seed):
    # num_clients >= num_classes so each shard spans ~1 label
    _, y = make_synthetic_classification(num_clients * 40, seed=seed)
    splits = non_iid_split(y, num_clients, labels_per_client=lpc, seed=seed)
    assert len(splits) == num_clients
    all_idx = np.concatenate(splits)
    assert len(np.unique(all_idx)) == len(all_idx), "no sample reuse"
    # shard-based split: a client holds lpc runs of the label-sorted
    # samples; a run of L samples covers the tail of one label, at most
    # (L - 2) // (smallest class) whole labels and the head of another
    run = -(-len(y) // (num_clients * lpc))
    per_shard = 2 + (run - 2) // int(np.bincount(y).min())
    for s in splits:
        assert len(np.unique(y[s])) <= lpc * per_shard


def test_federated_dataset_shapes():
    d = FederatedDataset.synthetic(10, kind="mnist", samples_per_client=50,
                                   test_samples=100)
    assert len(d.clients) == 10
    rng = np.random.default_rng(0)
    b = d.clients[0].sample_batches(rng, 8, 3)
    assert b["x"].shape[:2] == (3, 8)
    assert b["y"].shape == (3, 8)


def test_token_shards_non_iid():
    shards = client_token_shards(4, vocab_size=1000, seq_len=16, batch_size=2)
    rng = np.random.default_rng(0)
    b0 = shards[0].sample(rng)
    b1 = shards[3].sample(rng)
    assert b0["tokens"].shape == (2, 16)
    np.testing.assert_array_equal(b0["tokens"][:, 1:], b0["labels"][:, :-1])
    assert b0["tokens"].min() >= 0 and b0["tokens"].max() < 1000
    assert abs(b0["tokens"].mean() - b1["tokens"].mean()) > 1  # topic bias


def test_checkpoint_roundtrip_and_latest():
    tree = {"a": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
            "b": [jnp.ones(3), {"c": jnp.zeros((2,), jnp.int32)}],
            "t": (jnp.ones(1), jnp.zeros(1))}
    with tempfile.TemporaryDirectory() as d:
        save_pytree(d, tree, step=3)
        p10 = save_pytree(d, tree, step=10)
        assert latest_checkpoint(d) == p10
        r = restore_pytree(p10)
        assert isinstance(r["b"], list) and isinstance(r["t"], tuple)
        np.testing.assert_allclose(np.asarray(r["a"], np.float32),
                                   np.asarray(tree["a"], np.float32))
        assert r["b"][1]["c"].dtype == np.int32
