"""The main path's Pallas kernels compile for a TPU v5e at real sizes.

Interpret mode checks a kernel's logic but not what Mosaic accepts:
block shapes, reshapes across the lane tiling, VMEM. These tests lower
each kernel with ``interpret=False`` for a *described* ``v5e:2x2`` chip
(no chip needed; the TPU compiler ships with jaxlib) and assert the
compiled program holds the Mosaic call. Sizes are the ones the presets
run: the paper cohort (N=50, M=3), ``metropolis-1k`` (1000, 12) and one
client shard of ``metropolis-100k`` on four chips (25000, 32); the
aggregation sizes are the logreg and CNN parameter counts. Every tile
the ``best_tile`` autotuners may pick is compiled too.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.budgeted_topk import budgeted_topk, flgreedy_topk
from repro.kernels.budgeted_topk.kernel import density_sort_kernel
from repro.kernels.context_pairwise.kernel import context_pairwise_kernel
from repro.kernels.masked_aggregate.kernel import masked_aggregate_kernel

SELECT_SIZES = [(50, 3), (1000, 12), (25000, 32)]
TOPK_TILES = (64, 128, 256)
CONTEXT_TILES = (64, 128, 256, 512)
AGG_TILES = (256, 512, 1024, 2048)
# logreg 784x10 (+bias), the 2x conv(64) / FC 384 / FC 192 CNN, and the
# 16-d "tiny" model of the >=10^4-client cohorts
AGG_SIZES = [(5, 7850), (16, 7850), (5, 1_756_426), (16, 170)]
PHYS = dict(tx_w=0.2, noise_psd_w=3.98e-21, update_bits=1e5, workload=1e7)


@pytest.fixture(scope="module")
def topo():
    import importlib.util

    from jax.experimental import topologies
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) installed")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled.as_text()


@pytest.mark.parametrize("n,m", SELECT_SIZES)
def test_density_sort_compiles(one_chip, n, m):
    args = (_shape(one_chip, (n, m)), _shape(one_chip, (n,)),
            _shape(one_chip, (n, m), bool))
    for tile in TOPK_TILES:
        text = _compiled_text(
            lambda v, c, e, tile=tile: density_sort_kernel(
                v, c, e, tile=tile, interpret=False), *args)
        assert "tpu_custom_call" in text, (n, m, tile)


@pytest.mark.parametrize("seeds", [0, 2])
@pytest.mark.parametrize("n,m,sqrt_utility", [(50, 3, True),
                                              (1000, 12, False)])
def test_budgeted_selection_compiles(one_chip, n, m, sqrt_utility, seeds):
    """The whole solver — kernel plus budget walk — as the policies call
    it: P3 (flgreedy) at the paper size, P2 at metropolis-1k; with
    ``seeds`` > 0 vmapped over a seed axis, as the fused engine runs it."""
    solve = flgreedy_topk if sqrt_utility else budgeted_topk
    lead = (seeds,) if seeds else ()
    args = (_shape(one_chip, lead + (n, m)), _shape(one_chip, lead + (n,)),
            _shape(one_chip, lead + (m,)),
            _shape(one_chip, lead + (n, m), bool))

    def fn(v, c, b, e):
        return solve(v, c, b, e, use_kernel=True, tile=128,
                     interpret=False)
    text = _compiled_text(jax.vmap(fn) if seeds else fn, *args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,m", SELECT_SIZES)
def test_context_pairwise_compiles(one_chip, n, m):
    args = (_shape(one_chip, (n, 2)), _shape(one_chip, (m, 2)),
            _shape(one_chip, (n,)), _shape(one_chip, (n,)),
            _shape(one_chip, (n, m)), _shape(one_chip, (n, m)))
    for tile in CONTEXT_TILES:
        text = _compiled_text(
            lambda *a, tile=tile: context_pairwise_kernel(
                *a, tile=tile, interpret=False, **PHYS), *args)
        assert "tpu_custom_call" in text, (n, m, tile)


def test_context_pairwise_compiles_vmapped(one_chip):
    """The env stage runs the kernel vmapped over seeds."""
    s, n, m = 2, 1000, 12
    args = (_shape(one_chip, (s, n, 2)), _shape(one_chip, (s, m, 2)),
            _shape(one_chip, (s, n)), _shape(one_chip, (s, n)),
            _shape(one_chip, (s, n, m)), _shape(one_chip, (s, n, m)))
    text = _compiled_text(jax.vmap(
        lambda *a: context_pairwise_kernel(*a, tile=128, interpret=False,
                                           **PHYS)), *args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("c,d", AGG_SIZES)
def test_masked_aggregate_compiles(one_chip, c, d):
    args = (_shape(one_chip, (d,)), _shape(one_chip, (c, d)),
            _shape(one_chip, (c,)))
    for tile in AGG_TILES:
        text = _compiled_text(
            lambda p, dl, w, tile=tile: masked_aggregate_kernel(
                p, dl, w, tile=tile, interpret=False), *args)
        assert "tpu_custom_call" in text, (c, d, tile)


@pytest.mark.parametrize("sqrt_utility", [False, True])
def test_sharded_selection_compiles_on_four_chips(topo, one_chip,
                                                  sqrt_utility):
    """``metropolis-100k`` over a (1, 4) ("seed", "clients") mesh: the
    kernel inside ``shard_map`` plus the per-pick ``all_gather`` merge
    and the ``pmax`` lockstep over the seed axis."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.mesh.select import shard_assign

    n, m = 100_000, 32
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4),
                ("seed", "clients"))

    def body(v, c, e, b):
        return shard_assign(v[0], c[0], e[0], b[0], axis_name="clients",
                            num_clients=n, sqrt_utility=sqrt_utility,
                            sync_axes=("seed",), use_kernel=True,
                            tile=128, interpret=False)[None]

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("seed", "clients"), P("seed", "clients"),
                  P("seed", "clients"), P("seed")),
        out_specs=P("seed", "clients"), check_vma=False)
    sc = NamedSharding(mesh, P("seed", "clients"))
    so = NamedSharding(mesh, P("seed"))
    args = (_shape(sc, (1, n, m)), _shape(sc, (1, n)),
            _shape(sc, (1, n, m), bool), _shape(so, (1, m)))
    text = _compiled_text(fn, *args)
    assert "tpu_custom_call" in text
    # the compiler may lower the scalar champion all_gather to all-reduce
    assert "all-gather" in text or "all-reduce" in text
