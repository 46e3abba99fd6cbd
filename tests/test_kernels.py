"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret=True
executes the Pallas kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.masked_aggregate.kernel import masked_aggregate_kernel
from repro.kernels.masked_aggregate.ops import masked_aggregate
from repro.kernels.masked_aggregate.ref import masked_aggregate_ref
from repro.kernels.rwkv6_scan.kernel import rwkv6_scan_kernel
from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref


@pytest.mark.parametrize("c,d,dtype", [
    (4, 257, jnp.float32), (8, 1024, jnp.float32), (16, 4096, jnp.float32),
    (5, 777, jnp.bfloat16), (1, 512, jnp.float32), (32, 130, jnp.bfloat16),
])
def test_masked_aggregate_shapes(c, d, dtype):
    key = jax.random.PRNGKey(c * 1000 + d)
    ks = jax.random.split(key, 3)
    p = jax.random.normal(ks[0], (d,), dtype)
    deltas = jax.random.normal(ks[1], (c, d), dtype)
    w = (jax.random.uniform(ks[2], (c,)) > 0.4).astype(jnp.float32)
    a = masked_aggregate_kernel(p, deltas, w, tile=256)
    b = masked_aggregate_ref(p, deltas, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=tol, rtol=tol)


def test_masked_aggregate_all_dropped():
    """Zero weights: aggregate must equal the original parameters."""
    p = jnp.arange(100, dtype=jnp.float32)
    deltas = jnp.ones((4, 100))
    w = jnp.zeros((4,))
    out = masked_aggregate_kernel(p, deltas, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(p))


def test_masked_aggregate_pytree_wrapper():
    tree = {"a": jnp.ones((3, 5)), "b": [jnp.zeros((7,))]}
    deltas = {"a": jnp.ones((2, 3, 5)), "b": [jnp.ones((2, 7))]}
    w = jnp.array([1.0, 1.0])
    out = masked_aggregate(tree, deltas, w, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out["a"]), 2 * np.ones((3, 5)))
    np.testing.assert_allclose(np.asarray(out["b"][0]), np.ones(7))


@pytest.mark.parametrize("b,h,kv,s,d,causal,win,dtype", [
    (1, 4, 2, 128, 64, True, 0, jnp.float32),
    (2, 4, 1, 256, 64, True, 0, jnp.float32),
    (1, 2, 2, 128, 64, False, 0, jnp.float32),
    (1, 4, 2, 256, 64, True, 64, jnp.float32),
    (1, 2, 1, 100, 32, True, 0, jnp.float32),   # padded seq
    (1, 2, 2, 128, 64, True, 0, jnp.bfloat16),
])
def test_flash_attention_vs_ref(b, h, kv, s, d, causal, win, dtype):
    key = jax.random.PRNGKey(b + h + s)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, kv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kv, s, d), dtype)
    out = flash_attention_kernel(q, k, v, causal=causal, window=win,
                                 block_q=64, block_k=64)
    ref = attention_ref(q, k, v, causal=causal, window=win)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-6
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("b,h,t,dk,dv,chunk", [
    (2, 2, 128, 32, 32, 32), (1, 3, 256, 64, 64, 64), (2, 1, 64, 16, 48, 16),
    (1, 1, 32, 8, 8, 32),
])
def test_rwkv6_scan_vs_ref(b, h, t, dk, dv, chunk):
    key = jax.random.PRNGKey(t + dk)
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (b, h, t, dk))
    k = jax.random.normal(ks[1], (b, h, t, dk))
    v = jax.random.normal(ks[2], (b, h, t, dv))
    lw = -jnp.exp(jax.random.normal(ks[3], (b, h, t, dk)) * 0.5 - 2.0)
    u = jax.random.normal(ks[4], (h, dk)) * 0.2
    y1, f1 = rwkv6_scan_kernel(r, k, v, lw, u, chunk=chunk)
    y2, f2 = rwkv6_scan_ref(r, k, v, lw, u)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2),
                               atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("interpret,want", [(None, True), (True, True),
                                            (False, False)])
def test_resolve_interpret_follows_backend(interpret, want):
    """``interpret=None`` (every kernel wrapper's default) resolves like
    ``resolve_kernel_mode``: interpreted off-TPU, compiled on a TPU."""
    from repro.kernels.common import resolve_interpret, resolve_kernel_mode
    assert jax.default_backend() == "cpu"
    assert resolve_interpret(interpret) is want
    assert resolve_kernel_mode(None) == (False, True)


def test_fastest_times_kernels_from_inside_a_trace():
    """The ``best_tile`` autotuners run at trace time: ``fastest`` must
    execute its Pallas calls (not stage them into the caller's jaxpr)
    and leave the kernel body's own tracing intact."""
    from repro.kernels.common import fastest
    picks = []

    def inputs():
        return (jnp.zeros((2048,)), jnp.ones((4, 2048)), jnp.ones((4,)))

    def run(tile, *args):
        return masked_aggregate_kernel(*args, tile=tile, interpret=True)

    def outer(x):
        picks.append(fastest((256, 512), inputs, run))
        return x + 1

    jaxpr = jax.make_jaxpr(jax.vmap(outer))(jnp.ones((2, 3)))
    assert picks[0] in (256, 512)
    assert "pallas_call" not in str(jaxpr)
