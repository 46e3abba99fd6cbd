"""Counter-based random-draw schedule shared by the host oracle and the
device simulator.

Every random quantity the HFL network simulator consumes — initial
positions, pricing, resource profiles, per-round mobility steps, resource
jitter, Rayleigh fading, the Monte-Carlo fading pairs behind ``true_p`` —
is drawn here from a threefry key schedule addressed by ``(seed, t,
tag)``. Draws are *unit-scale* (U[0,1), standard normal, Exp(1)); each
consumer applies its own scaling in its own precision.

Because the schedule is counter-based (no sequential generator state),
the host simulator (``repro.core.network.HFLNetworkSim``, numpy float64
math) and the device simulator (``repro.sim.core``, float32 XLA math
inside ``jit``/``scan``/``vmap``) consume *bitwise identical* float32
draws for the same ``(seed, t)`` — which is what makes device rollouts
comparable to the host oracle pointwise (to float tolerance) rather than
merely in distribution. Each draw has its own ``fold_in`` tag, so adding
or skipping a draw never shifts any other stream.

Host callers use ``host_init_draws`` / ``host_round_draws``: jitted once
per shape, returning numpy float64 upcasts of the same float32 draws.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax._src.prng import threefry2x32_p

# the randomness contract, stamped into RunResult provenance (repro.api):
# bump the suffix if tags, key derivation or draw shapes ever change
SCHEDULE_ID = "threefry2x32/(seed,t,tag)/v1"

# fold_in tags — frozen; append, never renumber
_INIT, _ROUND = 0, 1
_POS, _PRICE, _BW0, _COMP0, _PERM, _PHASE = 0, 1, 2, 3, 4, 5
_MOVE, _BWJ, _COMPJ, _FDT, _FUT, _MCDT, _MCUT = 0, 1, 2, 3, 4, 5, 6
# fault-injection streams (repro.sim.faults). Appended tags: with
# FaultSpec off these draws are simply never materialized, and because
# the schedule is counter-based, skipping them leaves every other
# stream bitwise unchanged.
_FDROP, _FSTRAG_U, _FSTRAG_E, _FOUT, _FCORR = 7, 8, 9, 10, 11


class InitDraws(NamedTuple):
    """Experiment-lifetime draws (all unit-scale)."""
    pos_u: jax.Array     # (N, 2) U[0,1) — initial positions
    price_u: jax.Array   # (N,)  U[0,1) — uniform price or tier selector
    bw_u: jax.Array      # (N,)  U[0,1) — base bandwidth profile
    comp_u: jax.Array    # (N,)  U[0,1) — base compute profile
    perm: jax.Array      # (N,)  int32 permutation — surge cohort draw
    phase_u: jax.Array   # (N,)  U[0,1) — bursty-arrival phase


class RoundDraws(NamedTuple):
    """Per-round draws (all unit-scale)."""
    move: jax.Array      # (N, 2) std normal — mobility step
    bw_n: jax.Array      # (N,)  std normal — bandwidth jitter
    comp_n: jax.Array    # (N,)  std normal — compute jitter
    fad_dt: jax.Array    # (N, M) Exp(1) — downlink Rayleigh |h|^2
    fad_ut: jax.Array    # (N, M) Exp(1) — uplink Rayleigh |h|^2
    mc_dt: jax.Array     # (K, N, M) Exp(1) — true_p Monte Carlo, downlink
    mc_ut: jax.Array     # (K, N, M) Exp(1) — true_p Monte Carlo, uplink


class FaultDraws(NamedTuple):
    """Per-round fault-event draws (all unit-scale).

    Event *thresholding* (``u < rate``) happens in float32 on both the
    host oracle and the device sim, so fault events match bitwise across
    backends — the same idiom as ``tier_edges``/``arrival_phases``.
    """
    drop_u: jax.Array    # (N,)  U[0,1) — client dropout events
    strag_u: jax.Array   # (N,)  U[0,1) — straggler events
    strag_e: jax.Array   # (N,)  Exp(1) — heavy-tail latency inflation
    out_u: jax.Array     # (M,)  U[0,1) — ES outage events
    corr_u: jax.Array    # (N,)  U[0,1) — update-corruption events


def init_key(seed) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(seed), _INIT)

def round_key(seed, t) -> jax.Array:
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(seed), _ROUND), t)


def init_draws(seed, n: int) -> InitDraws:
    k = init_key(seed)
    sub = functools.partial(jax.random.fold_in, k)
    return InitDraws(
        pos_u=jax.random.uniform(sub(_POS), (n, 2)),
        price_u=jax.random.uniform(sub(_PRICE), (n,)),
        bw_u=jax.random.uniform(sub(_BW0), (n,)),
        comp_u=jax.random.uniform(sub(_COMP0), (n,)),
        perm=jax.random.permutation(sub(_PERM), n).astype(jnp.int32),
        phase_u=jax.random.uniform(sub(_PHASE), (n,)),
    )


def round_draws(seed, t, n: int, m: int, k_mc: int) -> RoundDraws:
    k = round_key(seed, t)
    sub = functools.partial(jax.random.fold_in, k)
    return RoundDraws(
        move=jax.random.normal(sub(_MOVE), (n, 2)),
        bw_n=jax.random.normal(sub(_BWJ), (n,)),
        comp_n=jax.random.normal(sub(_COMPJ), (n,)),
        fad_dt=jax.random.exponential(sub(_FDT), (n, m)),
        fad_ut=jax.random.exponential(sub(_FUT), (n, m)),
        mc_dt=jax.random.exponential(sub(_MCDT), (k_mc, n, m)),
        mc_ut=jax.random.exponential(sub(_MCUT), (k_mc, n, m)),
    )


def fault_draws(seed, t, n: int, m: int) -> FaultDraws:
    k = round_key(seed, t)
    sub = functools.partial(jax.random.fold_in, k)
    return FaultDraws(
        drop_u=jax.random.uniform(sub(_FDROP), (n,)),
        strag_u=jax.random.uniform(sub(_FSTRAG_U), (n,)),
        strag_e=jax.random.exponential(sub(_FSTRAG_E), (n,)),
        out_u=jax.random.uniform(sub(_FOUT), (m,)),
        corr_u=jax.random.uniform(sub(_FCORR), (n,)),
    )


# -- shard-addressable slices of the dense streams --------------------------
#
# ``jax.random.uniform(key, shape)`` hashes each element's flat index
# ``i`` (``0 <= i < prod(shape)``) through threefry2x32 — the
# partitionable scheme, ``jax_threefry_partitionable``, the default of the
# installed JAX: the 64-bit counter ``(i >> 32, i & 0xffffffff)`` in, the
# xor of the two output words out. Because the schedule is counter-based,
# a client shard can evaluate the hash at exactly *its* flat indices and
# recover a bitwise-identical slice of the dense draw tensor without ever
# materializing the full ``(N, ...)`` array. These helpers replicate
# ``jax.random``'s bits and float conversions bit-for-bit;
# ``tests/test_mesh_select.py`` pins the parity against ``round_draws``.

def _bits_at(key, flat, total: int):
    """threefry2x32 bits at flat counter positions ``flat`` of a dense
    ``random_bits(key, 32, shape)`` stream, ``prod(shape) == total``
    (uint32)."""
    if not jax.config.jax_threefry_partitionable:
        raise NotImplementedError(
            "shard-sliced draws replicate the partitionable threefry "
            "stream; jax_threefry_partitionable is off")
    if total >= 2 ** 31:
        raise NotImplementedError(
            f"a dense stream of {total} draws overflows the int32 flat "
            "counters of the shard slices")
    k1 = lax.convert_element_type(key[0], jnp.uint32)
    k2 = lax.convert_element_type(key[1], jnp.uint32)
    f = jnp.asarray(flat, jnp.uint32)
    o1, o2 = threefry2x32_p.bind(k1, k2, jnp.zeros_like(f).ravel(),
                                 f.ravel())
    return (o1 ^ o2).reshape(f.shape)


def uniform_at(key, flat, total: int, lo=0.0, hi=1.0):
    """Slice of ``jax.random.uniform(key, shape, minval=lo, maxval=hi)``
    (f32, ``total = prod(shape)``) at flat positions ``flat``."""
    bits = _bits_at(key, flat, total)
    fbits = (bits >> np.uint32(9)) | np.uint32(0x3F800000)
    fl = lax.bitcast_convert_type(fbits, jnp.float32) - np.float32(1.0)
    return lax.max(np.float32(lo),
                   fl * (np.float32(hi) - np.float32(lo)) + np.float32(lo))


def normal_at(key, flat, total: int):
    """Slice of ``jax.random.normal(key, shape)`` (f32)."""
    lo = np.nextafter(np.float32(-1.0), np.float32(0.0), dtype=np.float32)
    u = uniform_at(key, flat, total, lo=lo, hi=1.0)
    return np.float32(np.sqrt(2)) * lax.erf_inv(u)


def exponential_at(key, flat, total: int):
    """Slice of ``jax.random.exponential(key, shape)`` (f32)."""
    return -jnp.log1p(-uniform_at(key, flat, total))


def _row_block(lo, n_local: int, cols: int, n: int):
    """Flat counters for rows ``lo .. lo+n_local`` of a dense ``(n, cols)``
    tensor (contiguous in the flat stream)."""
    del n  # rows are contiguous regardless of total row count
    start = jnp.asarray(lo, jnp.int32) * cols
    return start + jnp.arange(n_local * cols,
                              dtype=jnp.int32).reshape(n_local, cols)


def shard_round_draws(seed, t, n: int, m: int, k_mc: int,
                      lo, n_local: int) -> RoundDraws:
    """Rows ``lo .. lo+n_local`` of ``round_draws(seed, t, n, m, k_mc)``,
    bitwise, without materializing any dense ``(n, ...)`` tensor.

    ``lo`` may be traced (e.g. ``axis_index("clients") * n_local`` inside
    ``shard_map``); ``n_local`` must be static.
    """
    k = round_key(seed, t)
    sub = functools.partial(jax.random.fold_in, k)
    row1 = _row_block(lo, n_local, 1, n)[:, 0]
    # (k_mc, n, m) slices along axis 1 are strided in the flat stream
    mc_idx = (jnp.arange(max(k_mc, 1), dtype=jnp.int32)[:, None, None] * (n * m)
              + _row_block(lo, n_local, m, n)[None])
    def mc(tag):
        if k_mc == 0:
            return jnp.zeros((0, n_local, m), jnp.float32)
        return exponential_at(sub(tag), mc_idx, k_mc * n * m)
    return RoundDraws(
        move=normal_at(sub(_MOVE), _row_block(lo, n_local, 2, n), n * 2),
        bw_n=normal_at(sub(_BWJ), row1, n),
        comp_n=normal_at(sub(_COMPJ), row1, n),
        fad_dt=exponential_at(sub(_FDT), _row_block(lo, n_local, m, n), n * m),
        fad_ut=exponential_at(sub(_FUT), _row_block(lo, n_local, m, n), n * m),
        mc_dt=mc(_MCDT),
        mc_ut=mc(_MCUT),
    )


def shard_fault_draws(seed, t, n: int, m: int, lo, n_local: int) -> FaultDraws:
    """Rows ``lo .. lo+n_local`` of ``fault_draws(seed, t, n, m)``, bitwise.

    ``out_u`` is an ES-axis (M,) stream, small and identical on every
    shard, so it is drawn dense (replicated) rather than sliced.
    """
    k = round_key(seed, t)
    sub = functools.partial(jax.random.fold_in, k)
    row1 = _row_block(lo, n_local, 1, n)[:, 0]
    return FaultDraws(
        drop_u=uniform_at(sub(_FDROP), row1, n),
        strag_u=uniform_at(sub(_FSTRAG_U), row1, n),
        strag_e=exponential_at(sub(_FSTRAG_E), row1, n),
        out_u=jax.random.uniform(sub(_FOUT), (m,)),
        corr_u=uniform_at(sub(_FCORR), row1, n),
    )


# -- host access: jitted per shape, numpy float64 out -----------------------

@functools.lru_cache(maxsize=32)
def _jit_init(n: int):
    return jax.jit(functools.partial(init_draws, n=n))


@functools.lru_cache(maxsize=32)
def _jit_round_block(n: int, m: int, k_mc: int, block: int):
    """One dispatch realizing ``block`` consecutive rounds of draws
    (leading (block,) axis) — per-round dispatch + transfer overhead is
    what would otherwise dominate the host realizer."""
    def fn(seed, t0):
        ts = t0 + jnp.arange(block, dtype=jnp.int32)
        return jax.vmap(
            lambda t: round_draws(seed, t, n, m, k_mc))(ts)
    return jax.jit(fn)


def _to_host(tree):
    return jax.tree.map(
        lambda a: np.asarray(a, np.float64 if a.dtype == jnp.float32
                             else a.dtype), tree)


def host_init_draws(seed: int, n: int) -> InitDraws:
    """Float64 numpy view of the float32 init draws for ``seed``."""
    return _to_host(_jit_init(n)(jnp.uint32(seed)))


@functools.lru_cache(maxsize=32)
def _jit_fault(n: int, m: int):
    return jax.jit(functools.partial(fault_draws, n=n, m=m))


def host_fault_draws(seed: int, t: int, n: int, m: int) -> FaultDraws:
    """Float64 numpy view of the float32 round-``t`` fault draws.

    Small arrays (one (N,)/(M,) vector per stream), so no block cache:
    one jitted dispatch per round is cheap relative to the round draws.
    """
    return _to_host(_jit_fault(n, m)(jnp.uint32(seed), jnp.int32(t)))


# block-aligned cache of realized round draws, kept as float32 (the MC
# fading tensors dominate; upcast happens per round on access). Bounded
# FIFO: sequential consumers (rollouts, training loops) touch each block
# exactly once per seed, so a handful of entries suffices.
_BLOCK_TARGET = 2_000_000      # ~floats per cached block (f32: ~8 MB x2)
_block_cache: "dict" = {}
_BLOCK_CACHE_MAX = 8


def _block_size(n: int, m: int, k_mc: int) -> int:
    return max(1, min(32, _BLOCK_TARGET // max(1, k_mc * n * m)))


def host_round_draws(seed: int, t: int, n: int, m: int,
                     k_mc: int) -> RoundDraws:
    """Float64 numpy view of the float32 round-``t`` draws for ``seed``.

    Draws are realized in block-aligned batches of consecutive rounds
    (one jitted dispatch per block, sized to ~``_BLOCK_TARGET`` floats)
    and cached, so sequential ``round(t)`` consumers pay amortized
    per-round cost close to the raw threefry throughput."""
    block = _block_size(n, m, k_mc)
    bi, off = divmod(int(t), block)
    key = (int(seed), n, m, k_mc, bi)
    blk = _block_cache.get(key)
    if blk is None:
        blk = jax.tree.map(np.asarray, _jit_round_block(n, m, k_mc, block)(
            jnp.uint32(seed), jnp.int32(bi * block)))
        while len(_block_cache) >= _BLOCK_CACHE_MAX:
            _block_cache.pop(next(iter(_block_cache)))
        _block_cache[key] = blk
    return RoundDraws(*(np.asarray(a[off], np.float64)
                        if a.dtype == np.float32 else a[off]
                        for a in blk))
