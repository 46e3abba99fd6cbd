"""Pure-JAX HFL environment generator: Eq. 4-6 context realization as
jitted float32 functions, scannable over rounds and batched over seeds.

The round generator mirrors ``repro.core.network.HFLNetworkSim.round``
stage for stage — mobility update, client-ES association (+ stranded
fix), Eq. 4 Shannon rates, Eq. 5 compute+transmission latencies, Eq. 6
deadline outcomes, tiered/surge costs, context normalization, Monte-Carlo
``true_p`` — consuming the *same* counter-based draws
(``repro.sim.draws``), so a device rollout matches the host oracle
pointwise to float32 tolerance rather than merely in distribution.

Everything here is shape-static given a ``SimSpec``, so rollouts compile
once per (spec, horizon) and the per-round generator can be fused into
larger compiled regions (the experiment engine scans it inside its
training blocks — ``repro.experiment.fused``).

The Eq. 4/5 pairwise stage (distance -> gain -> rates -> latency) is
routed through ``repro.kernels.context_pairwise`` per
``SimSpec.use_kernel``: the default jnp oracle on CPU, one fused Pallas
launch per round on TPU (no HBM intermediates between the stages). Both
paths share the exact ``ref.py`` primitive sequence, so the switch is
bitwise-invisible to policies downstream.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_hfl import HFLExperimentConfig
from repro.core.network import es_positions
from repro.envs.scenarios import ScenarioSpec
from repro.kernels.common import resolve_kernel_mode
from repro.kernels.context_pairwise.ops import pairwise_context
from repro.kernels.context_pairwise.ref import latency, shannon_rate
from repro.obs import scopes
from repro.policies.base import Round
from repro.sim import draws
from repro.sim.spec import SimSpec
from repro.sim.truep import analytic_true_p


class SimStatics(NamedTuple):
    """Experiment-lifetime per-client arrays (float32, device-resident)."""
    pos0: jax.Array           # (N, 2) initial positions
    price: jax.Array          # (N,)
    base_bw: jax.Array        # (N,)
    base_comp: jax.Array      # (N,)
    surge_mask: jax.Array     # (N,) bool — flash-crowd cohort
    arrival_phase: jax.Array  # (N,) int32 — bursty-arrival phase


class SimRound(NamedTuple):
    """One realized round: the policy-facing ``Round`` fields plus the
    per-client resource vectors (``RoundData``'s extra columns)."""
    round: Round
    compute: jax.Array        # (N,)
    bandwidth: jax.Array      # (N,)


def _es_pos(spec: SimSpec) -> jnp.ndarray:
    return jnp.asarray(es_positions(spec.num_edge_servers), jnp.float32)


@jax.named_scope(scopes.ENV)
def init_statics(spec: SimSpec, seed) -> SimStatics:
    """Device twin of ``HFLNetworkSim.__init__``/``ScenarioSim.__init__``
    (same draws, float32 math)."""
    n = spec.num_clients
    di = draws.init_draws(seed, n)
    pos0 = -spec.area + di.pos_u * (2.0 * spec.area)
    if spec.price_tier_values is not None:
        edges = jnp.asarray(spec.price_tier_edges, jnp.float32)
        values = jnp.asarray(spec.price_tier_values, jnp.float32)
        idx = jnp.searchsorted(edges, di.price_u, side="right")
        price = values[jnp.minimum(idx, len(values) - 1)]
    else:
        price = spec.price_low + di.price_u * (spec.price_high
                                               - spec.price_low)
    base_bw = spec.bandwidth_low + di.bw_u * (spec.bandwidth_high
                                              - spec.bandwidth_low)
    base_comp = spec.compute_low + di.comp_u * (spec.compute_high
                                                - spec.compute_low)
    if spec.surge_count > 0:
        surge_mask = jnp.zeros((n,), bool).at[di.perm[:spec.surge_count]
                                              ].set(True)
    else:
        surge_mask = jnp.zeros((n,), bool)
    if spec.arrival_period > 0:
        phase = jnp.minimum(
            (di.phase_u * spec.arrival_period).astype(jnp.int32),
            spec.arrival_period - 1)
    else:
        phase = jnp.zeros((n,), jnp.int32)
    return SimStatics(pos0=pos0, price=price, base_bw=base_bw,
                      base_comp=base_comp, surge_mask=surge_mask,
                      arrival_phase=phase)


def _shannon_rate(spec: SimSpec, bandwidth, fading, g0):
    # delegates to the kernel package's oracle so simulator, Pallas body
    # and oracle share one float32 primitive sequence (bitwise parity)
    return shannon_rate(bandwidth, fading, g0, tx_w=spec.tx_w,
                        noise_psd_w=spec.noise_psd_w)


def _latency(spec: SimSpec, bandwidth, compute, fad_dt, fad_ut, g0):
    return latency(bandwidth, compute, fad_dt, fad_ut, g0, tx_w=spec.tx_w,
                   noise_psd_w=spec.noise_psd_w,
                   update_bits=spec.update_bits, workload=spec.workload)


@jax.named_scope(scopes.ENV)
def sim_round(spec: SimSpec, seed, statics: SimStatics, pos, t,
              dr: Optional[draws.RoundDraws] = None,
              fd: Optional[draws.FaultDraws] = None,
              ) -> Tuple[jax.Array, SimRound]:
    """One round of the network simulator: ``(pos, t) -> (pos', round)``.

    Pure and shape-static: the only carried state is the (N, 2) mobility
    positions; all randomness is re-derived from ``(seed, t)``.

    ``dr``/``fd`` override the internally derived draws: the sharded
    cohort engine (``repro.mesh``) passes shard-local slices
    (``draws.shard_round_draws``) together with shard-local ``statics``/
    ``pos`` rows, and every stage below is row-local, so the shard output
    is a bitwise row-slice of the dense round. The client count is taken
    from ``pos`` (local rows), never ``spec.num_clients`` (global).
    """
    n, m = pos.shape[0], spec.num_edge_servers
    t = jnp.asarray(t, jnp.int32)
    analytic = spec.true_p == "analytic"
    if dr is None:
        # analytic mode draws zero MC fading pairs: the (K, N, M) tensors
        # are the round generator's dominant cost, and the tags are
        # counter-based so skipping them never shifts any other stream
        dr = draws.round_draws(seed, t, n, m,
                               0 if analytic else spec.mc_true_p)
    pos = jnp.clip(pos + spec.mobility * dr.move, -spec.area, spec.area)
    es = _es_pos(spec)
    bandwidth = jnp.clip(statics.base_bw * (1 + spec.jitter * dr.bw_n),
                         spec.bandwidth_low, spec.bandwidth_high)
    compute = jnp.clip(statics.base_comp * (1 + spec.jitter * dr.comp_n),
                       spec.compute_low, spec.compute_high)
    # fused Eq. 4/5 stage: distance -> gain -> rates -> latency in one
    # pass (a single Pallas launch when the spec routes to the kernel)
    use_k, interp = resolve_kernel_mode(spec.use_kernel)
    d, g0, mean_rate, tau = pairwise_context(
        pos, es, bandwidth, compute, dr.fad_dt, dr.fad_ut, tx_w=spec.tx_w,
        noise_psd_w=spec.noise_psd_w, update_bits=spec.update_bits,
        workload=spec.workload, use_kernel=use_k, tile=spec.kernel_tile,
        interpret=interp)
    eligible = d <= spec.cell_radius_km
    # stranded fix: a client covering no ES is attached to the nearest one
    nearest = jax.nn.one_hot(jnp.argmin(d, axis=1), m, dtype=bool)
    eligible = eligible | (~eligible.any(axis=1, keepdims=True) & nearest)
    costs = 2.0 * statics.price * bandwidth / 1e6
    if spec.surge_period > 0:
        surge_on = (t % spec.surge_period) < spec.surge_len
        costs = jnp.where(surge_on & statics.surge_mask,
                          costs * spec.surge_discount, costs)
    if spec.arrival_period > 0:
        active = ((t - statics.arrival_phase) % spec.arrival_period
                  < spec.arrival_len)
        eligible = eligible & active[:, None]
    if spec.faults is not None and spec.faults.enabled:
        # identical fault events as the host oracle: shared counter-based
        # draws, float32 thresholds on both sides (repro.sim.faults)
        from repro.sim.faults import apply_latency_faults, apply_outage
        if fd is None:
            fd = draws.fault_draws(seed, t, n, m)
        tau = apply_latency_faults(spec.faults, tau, fd.strag_u,
                                   fd.strag_e, fd.drop_u, jnp)
        eligible = apply_outage(spec.faults, eligible, fd.out_u, jnp)
    outcomes = (tau <= spec.deadline_s).astype(jnp.float32)
    phi_rate = jnp.clip(mean_rate / spec.rate_hi, 0.0, 1.0)
    phi_comp = ((compute - spec.compute_low)
                / (spec.compute_high - spec.compute_low))
    contexts = jnp.stack(
        [phi_rate, jnp.broadcast_to(phi_comp[:, None], (n, m))], axis=-1)
    if analytic:
        true_p = analytic_true_p(
            bandwidth[:, None], compute[:, None], g0, tx_w=spec.tx_w,
            noise_psd_w=spec.noise_psd_w, update_bits=spec.update_bits,
            workload=spec.workload, deadline_s=spec.deadline_s, xp=jnp)
        true_p = true_p.astype(jnp.float32)
    else:
        tau_mc = _latency(spec, bandwidth[None, :, None],
                          compute[None, :, None], dr.mc_dt, dr.mc_ut,
                          g0[None])
        true_p = jnp.mean((tau_mc <= spec.deadline_s).astype(jnp.float32),
                          axis=0)
    rd = Round(t=t, contexts=contexts.astype(jnp.float32),
               eligible=eligible, costs=costs.astype(jnp.float32),
               outcomes=outcomes, true_p=true_p,
               latency=tau.astype(jnp.float32))
    return pos, SimRound(round=rd, compute=compute, bandwidth=bandwidth)


@jax.named_scope(scopes.ENV)
def round_batch(spec: SimSpec, seeds, statics: SimStatics, pos, t
                ) -> Tuple[jax.Array, Round]:
    """Seed-batched round generation for fused scans: ``seeds``/``statics``
    /``pos`` carry a leading (S,) axis, ``t`` is the shared scalar round
    index. Returns ``(pos', Round)`` with (S, ...) leaves (``rd.t`` is
    (S,), matching the stacked host layout)."""
    pos, sr = jax.vmap(
        lambda se, st, p: sim_round(spec, se, st, p, t))(seeds, statics, pos)
    return pos, sr.round


@functools.lru_cache(maxsize=64)
def _compiled_rollout(spec: SimSpec, horizon: int, multi: bool):
    def run(seed, t0):
        statics = init_statics(spec, seed)

        def step(pos, t):
            pos, sr = sim_round(spec, seed, statics, pos, t)
            return pos, sr

        _, rounds = jax.lax.scan(step, statics.pos0,
                                 t0 + jnp.arange(horizon, dtype=jnp.int32))
        return rounds
    if multi:
        run = jax.vmap(run, in_axes=(0, None))
    return jax.jit(run)


def rollout_device(spec: SimSpec, seeds: Sequence[int], horizon: int,
                   t0: int = 0) -> SimRound:
    """Whole seed sweep on device: ``SimRound`` pytree with (S, T, ...)
    leaves (single dispatch, one executable per (spec, horizon))."""
    seed_arr = jnp.asarray(np.asarray(seeds, np.uint32))
    return _compiled_rollout(spec, int(horizon), True)(
        seed_arr, jnp.int32(t0))


@functools.lru_cache(maxsize=64)
def _compiled_statics(spec: SimSpec, multi: bool):
    fn = functools.partial(init_statics, spec)
    return jax.jit(jax.vmap(fn) if multi else fn)


def init_statics_multi(spec: SimSpec, seeds: Sequence[int]) -> SimStatics:
    """Per-seed statics stacked on a leading (S,) axis (one dispatch)."""
    return _compiled_statics(spec, True)(
        jnp.asarray(np.asarray(seeds, np.uint32)))


# -- the environment object -------------------------------------------------


@dataclass(frozen=True)
class SimEnvState:
    seed: int
    statics: SimStatics
    pos: jax.Array
    t: int = 0


@dataclass(frozen=True)
class DeviceEnv:
    """Device-resident twin of ``repro.envs.base.HFLEnv``: same
    (config, scenario) pairing and init/step/rollout contract, with the
    round generator compiled to XLA instead of realized on host."""
    cfg: HFLExperimentConfig
    scenario: ScenarioSpec
    spec: SimSpec

    @property
    def name(self) -> str:
        return self.scenario.name

    def host_env(self):
        """The host parity oracle over the same (cfg, scenario) — fault
        injection included, so parity extends to faulty worlds."""
        from repro.envs.base import HFLEnv
        return HFLEnv(cfg=self.cfg, spec=self.scenario,
                      true_p=self.spec.true_p, faults=self.spec.faults)

    def make_sim(self, seed: int = 0):
        return self.host_env().make_sim(seed)

    def init(self, seed: int = 0) -> SimEnvState:
        statics = _compiled_statics(self.spec, False)(jnp.uint32(seed))
        return SimEnvState(seed=int(seed), statics=statics,
                           pos=statics.pos0, t=0)

    def step(self, state: SimEnvState,
             t: Optional[int] = None) -> Tuple[SimEnvState, Round]:
        """Pure single-round step (eager dispatch of the jitted round)."""
        tt = state.t if t is None else t
        pos, sr = _jitted_round(self.spec)(
            jnp.uint32(state.seed), state.statics, state.pos,
            jnp.int32(tt))
        return (SimEnvState(seed=state.seed, statics=state.statics,
                            pos=pos, t=tt + 1), sr.round)

    def rollout_device(self, seeds: Sequence[int],
                       horizon: int) -> SimRound:
        return rollout_device(self.spec, seeds, horizon)

    def rollout_multi(self, seeds: Sequence[int], horizon: int) -> Round:
        """Drop-in for ``HFLEnv.rollout_multi``: a stacked (S, T, ...)
        ``Round`` batch — realized on device, leaves stay jnp arrays."""
        return self.rollout_device(seeds, horizon).round

    def rollout(self, seed: int, horizon: int) -> List:
        """Host ``RoundData`` list (device-realized, then materialized) —
        the interop path for host-state policies and legacy drivers."""
        from repro.core.network import RoundData
        sr = self.rollout_device([seed], horizon)
        # one device->host transfer for the whole pytree (device_get),
        # then per-round zero-copy views into the stacked host arrays —
        # not one blocking np.asarray conversion per leaf
        host = jax.tree.map(lambda a: a[0], jax.device_get(sr))
        return [RoundData(t=int(host.round.t[i]),
                          contexts=host.round.contexts[i],
                          eligible=host.round.eligible[i],
                          costs=host.round.costs[i],
                          outcomes=host.round.outcomes[i],
                          true_p=host.round.true_p[i],
                          compute=host.compute[i],
                          bandwidth=host.bandwidth[i],
                          latency=host.round.latency[i])
                for i in range(horizon)]


@functools.lru_cache(maxsize=64)
def _jitted_round(spec: SimSpec):
    return jax.jit(functools.partial(sim_round, spec))
