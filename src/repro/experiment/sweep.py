"""``sweep_experiments``: whole multi-seed HFL experiments, one
compiled dispatch per eval interval — the engine behind the
``repro.run`` facade's training tiers (``run_experiment_sweep`` remains
as its deprecated alias).

Two environment modes share the driver:

* host env (``repro.envs.HFLEnv``): observables are realized per seed on
  host (``env.rollout``), stacked into an (S, T, ...) ``Round`` batch and
  scanned by ``fused_block``;
* device env (``repro.sim.DeviceEnv``, or ``env="device"`` /
  ``"device:<preset>"`` by string): context generation runs *inside* the
  fused per-interval scan (``fused_block_device``) — no
  ``stack_rounds_multi`` pre-realization, no (S, T, ...) host arrays —
  which is what makes 1000-client cohorts feasible. Slot capacity comes
  from a device-side bandit pre-scan (``repro.sim.engine``).

With more than one accelerator the seed axis shards end-to-end: carries,
per-seed env state and (host mode) the stacked rounds are placed with a
``NamedSharding`` over a 1-D ``("seed",)`` mesh, so the jitted blocks
partition across devices (GSPMD) with zero cross-seed communication.

Policies that are not jax-capable (CUCB, LinUCB, phased COCS) fall back
to a sequential per-seed loop over the same realized rounds (device envs
materialize them on demand), built on the host-loop batched backend —
same packing semantics, same metrics, so a sweep can mix device and host
policies in one result.
"""
from __future__ import annotations

import json
import os
import warnings
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Dict, List, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.utility import _policy_kwargs, realized_utility
from repro.data.federated import FederatedDataset
from repro.experiment.fused import fused_block, fused_block_device
from repro.fed.batched import (BatchedRoundEngine, bucketed_capacity,
                               make_round_spec)
from repro.fed.hfl import _eval_fn
from repro.models.logistic import make_loss_fn, make_model
from repro.obs import trace as obs_trace
from repro.policies.base import (FunctionalPolicy, PolicyAdapter, Round,
                                 rounds_to_scan_axes)
from repro.policies.engine import (run_rounds_multi_seed, stack_states)

# the synthetic fallback switches to the 16-d "tiny" kind from this many
# clients on (metropolis-scale cohorts, dense or sharded alike)
TINY_DATA_CLIENTS = 10_000


@dataclass
class SweepResult:
    """Per-policy, per-seed experiment trajectories."""
    policies: List[str]
    seeds: List[int]
    eval_rounds: np.ndarray                      # (E,) 1-based round ids
    accuracy: Dict[str, np.ndarray]              # (S, E)
    loss: Dict[str, np.ndarray]                  # (S, E)
    utilities: Dict[str, np.ndarray]             # (S, T)
    participants: Dict[str, np.ndarray]          # (S, T)
    selections: Dict[str, np.ndarray]            # (S, T, N)
    explored: Dict[str, np.ndarray] = field(default_factory=dict)
    # per-policy carry-health reports when the guard is on (see
    # ``sweep_experiments(health=...)``): {"checked": int, "events":
    # [{"interval": int, "round_end": int, "bad": [leaf names]}]}
    health: Dict[str, dict] = field(default_factory=dict)
    # per-policy on-device telemetry when ``telemetry=True``
    # (``repro.obs.telemetry``): {"series": {metric: (S, T)},
    # "totals": {metric: (S,)}, "summary": {scalars}}; None per policy
    # on paths without taps (host-loop fallback)
    telemetry: Dict[str, Optional[dict]] = field(default_factory=dict)

    def final_accuracy(self, name: str) -> np.ndarray:
        return self.accuracy[name][:, -1]


def _block_bounds(horizon: int, eval_every: int) -> List[int]:
    """Exclusive block ends: an eval after every ``eval_every`` rounds and
    after the final round (the ``HFLSimulation.run`` cadence)."""
    ends = [t + 1 for t in range(horizon)
            if (t + 1) % eval_every == 0 or t == horizon - 1]
    return ends


def _block_slots(selections: np.ndarray, num_es: int, ends: List[int],
                 bucket: int) -> List[int]:
    """Exact per-block slot capacity from pre-scanned selections.

    The policy step costs ~10 ms for a whole sweep on the bandit engine,
    so running it once *ahead* of the fused blocks buys the same
    per-block exact capacity the host-loop engine gets from seeing the
    assignments — without loosening the static-shape guarantee: the fused
    block re-runs the identical pure policy from the identical state, so
    its (traced) assignments are the ones measured here and can never
    overflow. Capacity is shared across seeds (max) and rounded up to
    ``bucket`` to bound the number of compiled variants.
    """
    s, t_len, n = selections.shape
    peaks = np.zeros(t_len, np.int64)
    for si in range(s):
        for t in range(t_len):
            a = selections[si, t]
            sel = a[a >= 0]
            if sel.size:
                peaks[t] = max(peaks[t],
                               int(np.bincount(sel, minlength=num_es).max()))
    out, lo = [], 0
    for hi in ends:
        peak = max(1, int(peaks[lo:hi].max()))
        out.append(bucketed_capacity(peak, bucket, n))
        lo = hi
    return out


class TrainingSetup(NamedTuple):
    """Everything the fused training paths derive from (cfg, model,
    data, seeds) — built in exactly one place so the sweep engine and
    the grid engine (``repro.api.grid``) cannot drift on the data-kind
    mapping, the per-seed model init, or the sampler key convention
    (``PRNGKey(seed + 11)``) their bitwise-parity contract rests on."""
    data: FederatedDataset
    stacked: object            # StackedClients device shards
    batch: int                 # batch size clamped to smallest shard
    steps: int                 # local SGD steps per round
    loss_fn: object
    logits_fn: object
    edge_seed: object          # (S, M, ...) per-seed initial edge params
    base_keys: jax.Array       # (S,) per-seed sampler keys
    spec: object               # BatchedRoundSpec
    test_x: jax.Array
    test_y: jax.Array


def prepare_training(cfg, model_kind: str, batch_size: int,
                     batches_per_epoch: int,
                     data: Optional[FederatedDataset],
                     seeds: Sequence[int],
                     use_kernel: Optional[bool] = None,
                     tile: Optional[int] = None,
                     aggregator: str = "mean", trim_frac: float = 0.1,
                     corrupt: bool = False) -> TrainingSetup:
    """Host-side training-state preparation shared by every fused path:
    synthetic-data default (shared ``seed=0`` dataset; ``"tiny"`` from
    ``TINY_DATA_CLIENTS`` clients on), stacked shards,
    per-seed model inits broadcast to (M, ...) edge params, per-seed
    sampler base keys, and the static round spec."""
    kind = "mnist" if model_kind.startswith("logreg") else "cifar"
    if data is None and kind == "mnist" and \
            cfg.num_clients >= TINY_DATA_CLIENTS:
        # metropolis scale: 784-d shards would need terabytes
        with obs_trace.span("data.synthetic_tiny",
                            clients=cfg.num_clients):
            data = FederatedDataset.synthetic(
                cfg.num_clients, kind="tiny", samples_per_client=20,
                seed=0)
    data = data or FederatedDataset.synthetic(cfg.num_clients, kind=kind,
                                              seed=0)
    stacked = data.stacked()
    sizes = np.asarray(stacked.sizes)
    batch = int(min(batch_size, sizes.min()))
    steps = cfg.local_epochs * batches_per_epoch
    loss_fn = make_loss_fn(model_kind)
    inits, logits_fn = [], None
    for s in seeds:
        params, logits_fn = make_model(
            model_kind, jax.random.PRNGKey(s),
            input_shape=data.test_x.shape[1:])
        inits.append(jax.tree.map(
            lambda p: jnp.broadcast_to(
                p[None], (cfg.num_edge_servers,) + p.shape), params))
    edge_seed = jax.tree.map(lambda *xs: jnp.stack(xs), *inits)
    param_count = sum(int(p.size) for p in
                      jax.tree.leaves(inits[0])) // cfg.num_edge_servers
    spec = make_round_spec(cfg, steps=steps, batch_size=batch_size,
                           use_kernel=use_kernel, tile=tile,
                           param_count=param_count, aggregator=aggregator,
                           trim_frac=trim_frac, corrupt=corrupt)
    base_keys = jnp.stack([jax.random.PRNGKey(s + 11) for s in seeds])
    return TrainingSetup(data=data, stacked=stacked, batch=batch,
                         steps=steps, loss_fn=loss_fn,
                         logits_fn=logits_fn, edge_seed=edge_seed,
                         base_keys=base_keys, spec=spec,
                         test_x=jnp.asarray(data.test_x),
                         test_y=jnp.asarray(data.test_y))


def _seed_mesh(n_seeds: int, shard_seeds: Optional[bool]):
    """A 1-D ("seed",) device mesh when sharding applies, else None."""
    if shard_seeds is False:
        return None
    devices = jax.devices()
    if len(devices) <= 1 or n_seeds % len(devices) != 0:
        if shard_seeds:
            warnings.warn(
                f"seed-axis sharding requested but {n_seeds} seeds do not "
                f"tile {len(devices)} device(s); running unsharded",
                stacklevel=3)
        return None
    return jax.sharding.Mesh(np.array(devices), ("seed",))


def _shard_seed_axis(tree, mesh, axis: int = 0):
    """Place every leaf with its ``axis`` dimension split over the seed
    mesh (no-op when the mesh is None)."""
    if mesh is None:
        return tree
    from jax.sharding import NamedSharding, PartitionSpec

    def put(a):
        spec = [None] * jnp.ndim(a)
        spec[axis] = "seed"
        return jax.device_put(a, NamedSharding(mesh,
                                               PartitionSpec(*spec)))
    return jax.tree.map(put, tree)


# -- resilient execution: checkpoint/resume + carry-health guards ------------
# The fused runners dispatch one compiled block per eval interval; the
# per-interval boundary is the natural checkpoint grain. A checkpoint is
# the *exact* scan carry (policy state, edge params, env positions) plus
# every completed interval's outputs and the interval index, written
# atomically — so a killed-and-resumed run replays the remaining blocks
# from the identical carry the uninterrupted run would have used and
# reproduces its policy decisions bitwise. A fingerprint (draw-schedule
# id, policy, spec, world, seeds, interval layout) guards against
# resuming into a different experiment.


class SimulatedKill(RuntimeError):
    """Raised after ``stop_after_blocks`` checkpointed intervals: a
    deterministic stand-in for killing the process mid-run (the resume
    tests and ``examples/fault_injection.py`` use it)."""


_OUT_FIELDS = ("accuracy", "loss", "utilities", "participants",
               "selections", "explored")


def _str_arr(s: str) -> np.ndarray:
    # checkpoint payloads hold only array leaves; strings ride as uint8
    return np.frombuffer(s.encode("utf-8"), np.uint8).copy()


def _arr_str(a) -> str:
    return bytes(np.asarray(a, np.uint8)).decode("utf-8")


@dataclass
class _ResilientCtx:
    """Per-policy state for the resilient fused runner."""
    ckpt_dir: Optional[str]          # None: health/kill hooks only
    resume: bool
    health: str                      # "off" | "record" | "halt"
    stop_after: Optional[int]
    fingerprint: str
    report: dict = field(default_factory=lambda: {"checked": 0,
                                                  "events": []})
    outs_np: list = field(default_factory=list)


def _run_fingerprint(name: str, spec, env, device_env: bool, seeds,
                     ends, slots_blocks, telemetry: bool = False) -> str:
    from repro.sim.draws import SCHEDULE_ID
    world = (repr(env.spec) if device_env
             else f"{env.name}/{env.cfg!r}/"
                  f"faults={getattr(env, 'faults', None)!r}")
    fp = {"schedule": SCHEDULE_ID, "policy": name,
          "spec": repr(spec), "world": world,
          "seeds": list(seeds), "ends": list(ends),
          "slots": list(slots_blocks)}
    if telemetry:
        # telemetry-on checkpoints carry extra out leaves; keep the
        # telemetry-off fingerprint byte-identical to the seed format
        fp["telemetry"] = True
    return json.dumps(fp, sort_keys=True)


def _like(template, restored):
    """Rebuild a restored carry in the template's pytree structure
    (tuples/NamedTuples degrade to lists in the msgpack payload)."""
    leaves_t, treedef = jax.tree.flatten(template)
    leaves_r = jax.tree.leaves(restored)
    if len(leaves_r) != len(leaves_t):
        raise ValueError(
            f"checkpoint carry has {len(leaves_r)} leaves, expected "
            f"{len(leaves_t)} — written by a different model or policy?")
    return jax.tree.unflatten(treedef, [jnp.asarray(r)
                                        for r in leaves_r])


def _out_np(o) -> dict:
    d = {k: np.asarray(getattr(o, k)) for k in _OUT_FIELDS}
    # telemetry taps (when on) checkpoint alongside the result streams,
    # as plain dicts of array leaves (msgpack payloads hold no classes)
    if getattr(o, "telemetry", None) is not None:
        from repro.obs.telemetry import TelemetryAcc, TelemetryFrame
        tele, acc = o.telemetry, o.tele_acc
        d["telemetry"] = {k: np.asarray(getattr(tele, k))
                          for k in TelemetryFrame._fields}
        d["tele_acc"] = {k: np.asarray(getattr(acc, k))
                         for k in TelemetryAcc._fields}
    return d


def _try_resume(ctx: _ResilientCtx, template: dict):
    """Load the newest checkpoint, verify its fingerprint, and return
    ``(blocks_done, carry, outs)`` — or None when there is nothing to
    resume from."""
    from repro.checkpoint import latest_checkpoint, restore_pytree
    if ctx.ckpt_dir is None:
        return None
    path = latest_checkpoint(ctx.ckpt_dir)
    if path is None:
        return None
    payload = restore_pytree(path)
    if _arr_str(payload["fingerprint"]) != ctx.fingerprint:
        raise ValueError(
            f"checkpoint {path!r} was written by a different run "
            "configuration (draw schedule / policy / spec / seeds / "
            "interval layout mismatch); refusing to resume — point "
            "checkpoint_dir at a fresh directory or disable resume")
    done = int(np.asarray(payload["blocks_done"]))
    carry = {k: _like(template[k], payload["carry"][k]) for k in template}
    ctx.outs_np = [dict(b) for b in payload["outs"]]
    ctx.report = json.loads(_arr_str(payload["health"]))
    return done, carry, [SimpleNamespace(**b) for b in ctx.outs_np]


def _bad_leaves(tag: str, tree) -> list:
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        if np.issubdtype(a.dtype, np.floating) and \
                not np.all(np.isfinite(a)):
            out.append(tag + jax.tree_util.keystr(path))
    return out


def _after_block(ctx: _ResilientCtx, bi: int, hi: int, carry: dict, out):
    """Post-interval bookkeeping: health scan, atomic checkpoint write,
    simulated kill. Materializing the carry costs one device sync per
    interval — the price of resilience; the ctx=None fast path keeps
    blocks in flight and never lands here."""
    from repro.checkpoint import save_pytree
    ctx.outs_np.append(_out_np(out))
    carry_np = jax.tree.map(np.asarray, carry)
    if ctx.health != "off":
        bad = (_bad_leaves("carry", carry_np)
               + _bad_leaves("out", ctx.outs_np[-1]))
        ctx.report["checked"] += 1
        if bad:
            ctx.report["events"].append(
                {"interval": bi, "round_end": hi, "bad": bad})
            # carry-guard findings join the telemetry event stream, so
            # a traced run shows them in-line with the block spans
            obs_trace.event("health", interval=bi, round_end=hi, bad=bad)
            if ctx.health == "halt":
                raise RuntimeError(
                    f"non-finite training state after interval {bi} "
                    f"(round {hi}): {bad} — run with health='record' to "
                    "log and continue instead")
    if ctx.ckpt_dir is not None:
        with obs_trace.span("checkpoint.save", interval=bi,
                            step=bi + 1):
            save_pytree(ctx.ckpt_dir, {
                "fingerprint": _str_arr(ctx.fingerprint),
                "blocks_done": np.int64(bi + 1),
                "carry": carry_np,
                "outs": list(ctx.outs_np),
                "health": _str_arr(json.dumps(ctx.report)),
            }, step=bi + 1)
    if ctx.stop_after is not None and bi + 1 >= ctx.stop_after:
        raise SimulatedKill(
            f"stop_after_blocks={ctx.stop_after}: run killed after "
            f"interval {bi + 1}"
            + ("" if ctx.ckpt_dir is None else
               f" (checkpoint {bi + 1} written to {ctx.ckpt_dir!r})"))


def sweep_experiments(policies: Union[Sequence[str],
                                      Dict[str, FunctionalPolicy]],
                      env, seeds: Sequence[int], horizon: int, *,
                      model_kind: str = "logreg", batch_size: int = 32,
                      batches_per_epoch: int = 2, eval_every: int = 5,
                      data: Optional[FederatedDataset] = None,
                      use_kernel: Optional[bool] = None,
                      tile: Optional[int] = None,
                      slots_per_es: Optional[int] = None,
                      shard_seeds: Optional[bool] = None,
                      policy_seed_offset: int = 0,
                      aggregator: str = "mean", trim_frac: float = 0.1,
                      checkpoint_dir: Optional[str] = None,
                      resume: bool = False, health: str = "off",
                      stop_after_blocks: Optional[int] = None,
                      telemetry: bool = False) -> SweepResult:
    """Run every policy for every seed over ``horizon`` training rounds.

    ``policies`` is either a dict name -> ``FunctionalPolicy`` or a list
    of registry names (constructed with the env config's COCS knobs, as
    ``HFLSimulation`` does). ``env`` is a host ``HFLEnv``, a device
    ``repro.sim.DeviceEnv``, or a string selector (``"paper"``,
    ``"device"``, ``"device:metropolis-1k"`` — see ``repro.sim.resolve``).
    Each seed gets its own realized environment, model init
    (``PRNGKey(seed)``), sampler stream and policy state — matching a
    ``HFLSimulation(seed=s)`` run with the same shared ``data`` — and
    jax-capable policies execute all seeds in one fused device program
    per eval interval (with env generation in-scan under a device env).
    ``policy_seed_offset`` shifts the policy init seeds relative to the
    env seeds (the legacy per-policy-name seeding of
    ``repro.core.utility.POLICY_TABLE``); the env, model and sampler
    streams stay keyed on the env seeds.

    Robustness knobs: ``aggregator``/``trim_frac`` select the Eq. 3
    edge-aggregation rule (``repro.fed.robust``); faults come from the
    env itself (``HFLEnv.faults`` / ``SimSpec.faults``). With
    ``checkpoint_dir`` set, the fused tiers write one atomic checkpoint
    per eval interval (per-policy subdirectory) and ``resume=True``
    continues a killed run from the newest one, reproducing the
    uninterrupted run's policy decisions bitwise. ``health`` guards each
    interval's carry/outputs for non-finite values ("record" logs into
    ``SweepResult.health``, "halt" raises). ``stop_after_blocks`` raises
    ``SimulatedKill`` after that many checkpointed intervals (test/demo
    hook). Host-loop policies run without the resilience hooks (warned).

    ``telemetry=True`` threads the ``repro.obs`` metric taps through the
    fused scans (observer-only: selections/utilities/explored stay
    bitwise identical) and fills ``SweepResult.telemetry`` per policy;
    host-loop policies report ``None`` there.

    This is the internal engine behind the ``repro.run`` facade; prefer
    ``repro.run(ExperimentSpec(...))`` in new code.
    """
    from repro import sim as simmod
    from repro.sim.core import DeviceEnv

    env = simmod.resolve(env)
    device_env = isinstance(env, DeviceEnv)
    cfg = env.cfg
    if health not in ("off", "record", "halt"):
        raise ValueError(
            f"health must be 'off', 'record' or 'halt', got {health!r}")
    faults = env.spec.faults if device_env else getattr(env, "faults",
                                                        None)
    corrupt = faults is not None and faults.corrupt_rate > 0.0
    resilient = (checkpoint_dir is not None or health != "off"
                 or stop_after_blocks is not None)
    seeds = [int(s) for s in seeds]
    pol_seeds = [s + int(policy_seed_offset) for s in seeds]
    if not isinstance(policies, dict):
        from repro import policies as _registry
        spec = _registry.PolicySpec.from_experiment(cfg, horizon)
        policies = {name: _registry.make(name, spec,
                                         **_policy_kwargs(cfg, name.lower()))
                    for name in policies}

    mesh = _seed_mesh(len(seeds), shard_seeds)

    # -- host-side data preparation ----------------------------------------
    # (for a device env the observables never touch the host: only model/
    #  policy initial states and the training data are staged here).
    # Realize exactly once: host-fallback policies need per-round
    # RoundData lists, fused policies the stacked batch — when both are
    # in the sweep, stack from the lists instead of re-realizing.
    any_host_pol = any(not p.jax_capable for p in policies.values())
    any_jax_pol = any(p.jax_capable for p in policies.values())
    rounds_per_seed = None          # host RoundData lists, realized lazily
    batch_st = scan_rounds = None
    if not device_env and any_jax_pol:
        with obs_trace.span("env.realize", seeds=len(seeds),
                            horizon=horizon):
            if any_host_pol:
                from repro.policies.engine import stack_rounds_multi
                rounds_per_seed = [env.rollout(s, horizon) for s in seeds]
                batch_st = stack_rounds_multi(rounds_per_seed)  # (S,T,...)
            else:
                batch_st = env.rollout_multi(seeds, horizon)    # (S,T,...)
            scan_rounds = rounds_to_scan_axes(batch_st)         # (T,S,...)
    with obs_trace.span("train.prepare", seeds=len(seeds),
                        model=model_kind):
        setup = prepare_training(cfg, model_kind, batch_size,
                                 batches_per_epoch, data, seeds,
                                 use_kernel=use_kernel, tile=tile,
                                 aggregator=aggregator,
                                 trim_frac=trim_frac, corrupt=corrupt)
    data, stacked, batch = setup.data, setup.stacked, setup.batch
    loss_fn, logits_fn = setup.loss_fn, setup.logits_fn
    edge0, base_keys, spec = setup.edge_seed, setup.base_keys, setup.spec
    test_x, test_y = setup.test_x, setup.test_y
    ends = _block_bounds(horizon, eval_every)
    if device_env:
        env_statics = simmod.init_statics_multi(env.spec, seeds)
        env_seeds = jnp.asarray(np.asarray(seeds, np.uint32))
        env_statics = _shard_seed_axis(env_statics, mesh)
        env_seeds = _shard_seed_axis(env_seeds, mesh)
    else:
        # slice per block on device; seed axis (axis 1) sharded
        env_seeds = _shard_seed_axis(
            jnp.asarray(np.asarray(seeds, np.uint32)), mesh)
        scan_rounds = _shard_seed_axis(jax.device_put(scan_rounds), mesh,
                                       axis=1)
    base_keys = _shard_seed_axis(base_keys, mesh)
    edge0 = _shard_seed_axis(edge0, mesh)

    def _realized_rounds():
        # host-policy fallback: per-round RoundData lists, realized once
        # on demand (device envs materialize theirs from a device rollout)
        nonlocal rounds_per_seed
        if rounds_per_seed is None:
            rounds_per_seed = [env.rollout(s, horizon) for s in seeds]
        return rounds_per_seed

    result = SweepResult(policies=list(policies), seeds=seeds,
                         eval_rounds=np.asarray(ends), accuracy={}, loss={},
                         utilities={}, participants={}, selections={},
                         explored={}, health={}, telemetry={})
    for name, pol in policies.items():
        if pol.jax_capable:
            if slots_per_es is not None:
                slots_blocks = [int(slots_per_es)] * len(ends)
            else:
                # bandit pre-scan (~ms): exact per-block slot capacity
                with obs_trace.span("slots.prescan", policy=name):
                    if device_env:
                        from repro.sim.engine import run_bandit_device
                        pre = run_bandit_device(pol, env.spec, seeds,
                                                horizon,
                                                policy_seeds=pol_seeds)
                    else:
                        pre = run_rounds_multi_seed(pol, batch_st,
                                                    pol_seeds)
                slots_blocks = _block_slots(
                    pre["selections"], cfg.num_edge_servers, ends,
                    spec.slot_bucket)
            ctx = None
            if resilient:
                pdir = None
                if checkpoint_dir is not None:
                    safe = "".join(c if c.isalnum() or c in "-_."
                                   else "_" for c in name)
                    pdir = os.path.join(checkpoint_dir, safe)
                ctx = _ResilientCtx(
                    ckpt_dir=pdir, resume=bool(resume), health=health,
                    stop_after=stop_after_blocks,
                    fingerprint=_run_fingerprint(
                        name, spec, env, device_env, seeds, ends,
                        slots_blocks, telemetry=telemetry))
            pstate = _shard_seed_axis(stack_states(pol, pol_seeds), mesh)
            if device_env:
                out = _run_fused_device(pol, spec, slots_blocks, batch,
                                        loss_fn, logits_fn, stacked,
                                        base_keys, pstate, edge0,
                                        env.spec, env_seeds, env_statics,
                                        test_x, test_y, ends, ctx=ctx,
                                        telemetry=telemetry)
            else:
                out = _run_fused(pol, spec, slots_blocks, batch, loss_fn,
                                 logits_fn, stacked, base_keys, pstate,
                                 edge0, scan_rounds, test_x, test_y, ends,
                                 faults=faults, env_seeds=env_seeds,
                                 ctx=ctx, telemetry=telemetry)
            if ctx is not None and health != "off":
                result.health[name] = ctx.report
        else:
            if resilient:
                warnings.warn(
                    "checkpoint/resume and health guards apply to the "
                    f"fused training tiers only; host-loop policy {name!r} "
                    "runs without them", stacklevel=2)
            out = _run_host(pol, spec, loss_fn, logits_fn, data, edge0,
                            _realized_rounds(), test_x, test_y, seeds,
                            pol_seeds, ends, slots_per_es, faults=faults)
        if pol.jax_capable and slots_per_es is not None:
            # a pinned capacity the solver exceeded would have silently
            # dropped the overflow clients from training (pack_assignment
            # scatters them into the discarded scratch slot) — fail loudly
            # like the host-loop engine's _slots_for does
            sels = out[4]
            peak = max((sels == j).sum(axis=-1).max()
                       for j in range(cfg.num_edge_servers))
            if peak > slots_per_es:
                raise ValueError(
                    f"{name}: a round assigned {peak} clients to one ES "
                    f"but slots_per_es={slots_per_es}; overflow clients "
                    "were dropped from training — raise slots_per_es or "
                    "leave it None for the exact pre-scan capacity")
        (result.accuracy[name], result.loss[name], result.utilities[name],
         result.participants[name], result.selections[name],
         result.explored[name], result.telemetry[name]) = out
    return result


def run_experiment_sweep(*args, **kwargs) -> SweepResult:
    """Deprecated alias of the sweep engine; use ``repro.run`` with an
    ``ExperimentSpec`` (``repro.api``) instead."""
    from repro.api.deprecation import warn_deprecated
    warn_deprecated("run_experiment_sweep",
                    "repro.run(ExperimentSpec(...)) / spec.grid(...)")
    return sweep_experiments(*args, **kwargs)


def _collect_blocks(outs, telemetry: bool = False):
    tele = None
    if telemetry:
        from repro.obs.telemetry import collect
        tele = collect([getattr(o, "telemetry", None) for o in outs],
                       [getattr(o, "tele_acc", None) for o in outs])
    return (np.stack([np.asarray(o.accuracy) for o in outs], axis=1),
            np.stack([np.asarray(o.loss) for o in outs], axis=1),
            np.concatenate([np.asarray(o.utilities) for o in outs], axis=1),
            np.concatenate([np.asarray(o.participants) for o in outs],
                           axis=1),
            np.concatenate([np.asarray(o.selections) for o in outs], axis=1),
            np.concatenate([np.asarray(o.explored) for o in outs], axis=1),
            tele)


def _traced_block(factory, make_args, bi, hi, lo, slots, attrs):
    """Dispatch one fused block under a tracer span (when active):
    records factory compile-cache hit/miss, whether this dispatch jit-
    compiled, and the dispatch (trace+compile) vs execute time split.
    With no tracer active this is the bare factory+call fast path — no
    sync, outputs stay in flight."""
    with obs_trace.span("fused_block" + attrs.pop("suffix", ""),
                        interval=bi, round_end=hi, rounds=hi - lo,
                        slots=slots, **attrs) as at:
        misses0 = factory.cache_info().misses
        fn, args = make_args()
        tr = obs_trace.active()
        if tr is None:
            return fn(*args)
        at["factory_hit"] = factory.cache_info().misses == misses0
        cache0 = fn._cache_size()
        t0 = obs_trace.now_us()
        out = fn(*args)
        at["dispatch_us"] = obs_trace.now_us() - t0
        t1 = obs_trace.now_us()
        jax.block_until_ready(out)
        at["execute_us"] = obs_trace.now_us() - t1
        at["compiled"] = fn._cache_size() > cache0
        return out


def _run_fused(pol, spec, slots_blocks, batch, loss_fn, logits_fn, stacked,
               base_keys, pstate, edge0, scan_rounds, test_x, test_y, ends,
               faults=None, env_seeds=None, ctx=None, telemetry=False):
    """All seeds at once: one fused dispatch per eval interval. Blocks are
    dispatched back-to-back with device outputs kept in flight; the host
    only materializes after the last block is enqueued (unless a
    resilient ``ctx`` syncs per interval for checkpoint/health, or an
    active tracer syncs to split dispatch/execute time)."""
    edge = jax.tree.map(jnp.copy, edge0)      # edge0 is reused per policy
    outs, start = [], 0
    if ctx is not None and ctx.resume:
        res = _try_resume(ctx, {"pstate": pstate, "edge": edge})
        if res is not None:
            start, carry, outs = res
            pstate, edge = carry["pstate"], carry["edge"]
    lo = ends[start - 1] if start > 0 else 0
    for bi in range(start, len(ends)):
        hi, slots = ends[bi], slots_blocks[bi]

        def make_args(lo=lo, slots=slots, pstate=pstate, edge=edge):
            fn = fused_block(pol, spec, slots, batch, loss_fn, logits_fn,
                             faults, telemetry)
            blk = Round(*(getattr(scan_rounds, f)[lo:ends[bi]]
                          for f in Round._fields))
            return fn, (stacked.x, stacked.y, stacked.sizes, base_keys,
                        pstate, edge, blk, test_x, test_y, env_seeds)

        out = _traced_block(fused_block, make_args, bi, hi, lo, slots,
                            {"policy": pol.name})
        pstate, edge = out.policy_state, out.edge_params
        outs.append(out)
        if ctx is not None:
            _after_block(ctx, bi, hi, {"pstate": pstate, "edge": edge},
                         out)
        lo = hi
    return _collect_blocks(outs, telemetry)


def _run_fused_device(pol, spec, slots_blocks, batch, loss_fn, logits_fn,
                      stacked, base_keys, pstate, edge0, sim_spec,
                      env_seeds, env_statics, test_x, test_y, ends,
                      ctx=None, telemetry=False):
    """Device-env twin of ``_run_fused``: each block generates its own
    rounds in-scan; the env's mobility positions thread through the
    blocks as a donated carry (``BlockOut.env_pos``)."""
    edge = jax.tree.map(jnp.copy, edge0)
    pos = jnp.copy(env_statics.pos0)
    outs, start = [], 0
    if ctx is not None and ctx.resume:
        res = _try_resume(ctx, {"pstate": pstate, "edge": edge,
                                "pos": pos})
        if res is not None:
            start, carry, outs = res
            pstate, edge, pos = (carry["pstate"], carry["edge"],
                                 carry["pos"])
    lo = ends[start - 1] if start > 0 else 0
    for bi in range(start, len(ends)):
        hi, slots = ends[bi], slots_blocks[bi]

        def make_args(lo=lo, slots=slots, pstate=pstate, edge=edge,
                      pos=pos):
            fn = fused_block_device(pol, spec, slots, batch, loss_fn,
                                    logits_fn, sim_spec, telemetry)
            return fn, (stacked.x, stacked.y, stacked.sizes, base_keys,
                        pstate, edge, pos, env_seeds, env_statics,
                        jnp.arange(lo, ends[bi], dtype=jnp.int32),
                        test_x, test_y)

        out = _traced_block(fused_block_device, make_args, bi, hi, lo,
                            slots, {"suffix": "_device",
                                    "policy": pol.name})
        pstate, edge, pos = out.policy_state, out.edge_params, out.env_pos
        outs.append(out)
        if ctx is not None:
            _after_block(ctx, bi, hi, {"pstate": pstate, "edge": edge,
                                       "pos": pos}, out)
        lo = hi
    return _collect_blocks(outs, telemetry)


def _run_host(pol, spec, loss_fn, logits_fn, data, edge0, rounds_per_seed,
              test_x, test_y, seeds, pol_seeds, ends, slots, faults=None):
    """Sequential fallback for host policies: per-seed adapter loop over
    the same realized rounds, training through the host-loop batched
    engine (per-block exact capacity unless ``slots`` pins one)."""
    eval_fn = _eval_fn(logits_fn)
    horizon = len(rounds_per_seed[0])
    n = rounds_per_seed[0][0].contexts.shape[0]
    accs = np.zeros((len(seeds), len(ends)))
    losses = np.zeros((len(seeds), len(ends)))
    utils = np.zeros((len(seeds), horizon))
    parts = np.zeros((len(seeds), horizon))
    sels = np.zeros((len(seeds), horizon, n), np.int64)
    expl = np.zeros((len(seeds), horizon), bool)
    for si, s in enumerate(seeds):
        adapter = PolicyAdapter(pol, seed=pol_seeds[si])
        engine = BatchedRoundEngine(spec, loss_fn, data, s,
                                    slots_per_es=slots, faults=faults)
        edge = jax.tree.map(lambda a: jnp.copy(a[si]), edge0)
        lo = 0
        for ei, hi in enumerate(ends):
            ts = list(range(lo, hi))
            rds = rounds_per_seed[si][lo:hi]
            assigns = []
            for t, rd in zip(ts, rds):
                assigns.append(adapter.step(rd))
                expl[si, t] = adapter.last_explored
            edge, p = engine.run_block(edge, assigns, rds, ts)
            for k, t in enumerate(ts):
                sels[si, t] = assigns[k]
                utils[si, t] = realized_utility(
                    assigns[k], rds[k], pol.spec.sqrt_utility)
            parts[si, lo:hi] = np.asarray(p)
            acc, loss = eval_fn(edge, test_x, test_y)
            accs[si, ei], losses[si, ei] = float(acc), float(loss)
            lo = hi
    # host-loop tier: no on-device taps (telemetry is a fused-scan
    # feature); callers see None and fall back gracefully
    return accs, losses, utils, parts, sels, expl, None
