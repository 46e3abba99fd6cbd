"""Observability is observer-only: ObsSpec telemetry taps leave every
tier's policy decisions and utilities bitwise unchanged, the on-device
accumulators match the host float64 oracle exactly, traces capture the
run lifecycle (including carry-health events) and render as a run
profile, and the shared logging setup keeps default output
print-compatible."""
import json
import os

import numpy as np
import pytest

import repro
from repro.api.run import build_env, build_policy
from repro.api.spec import (EnvSpec, EvalSpec, ExperimentSpec, PolicySpec,
                            TrainSpec)
from repro.experiment.sweep import SimulatedKill, sweep_experiments
from repro.obs import ObsSpec, logging_setup
from repro.obs.report import render_report
from repro.obs.trace import export_perfetto

HORIZON, EVERY = 16, 4
SEEDS = (0, 1)


def _spec(policy="COCS", backend="auto", train=True, telemetry=False,
          trace=None, perfetto=None, horizon=HORIZON, lr=None,
          health="off", checkpoint_dir=None, resume=False,
          aggregator="mean"):
    overrides = (("lr", lr),) if lr is not None else ()
    return ExperimentSpec(
        env=EnvSpec(scenario="paper", backend=backend, overrides=overrides),
        policy=PolicySpec(name=policy),
        train=(TrainSpec(model="logreg", aggregator=aggregator)
               if train else None),
        eval=EvalSpec(eval_every=EVERY, checkpoint_dir=checkpoint_dir,
                      resume=resume, health=health),
        obs=ObsSpec(telemetry=telemetry, trace=trace, perfetto=perfetto),
        horizon=horizon, seeds=SEEDS)


def _assert_same_decisions(a, b):
    np.testing.assert_array_equal(a.selections, b.selections)
    np.testing.assert_array_equal(a.utilities, b.utilities)
    np.testing.assert_array_equal(a.explored, b.explored)
    np.testing.assert_array_equal(a.participants, b.participants)
    if a.accuracy is not None or b.accuracy is not None:
        np.testing.assert_array_equal(a.accuracy, b.accuracy)
        np.testing.assert_array_equal(a.loss, b.loss)


@pytest.fixture(scope="module")
def fused_off():
    return repro.run(_spec())


@pytest.fixture(scope="module")
def fused_on():
    return repro.run(_spec(telemetry=True))


# -- bitwise neutrality, all four tiers ---------------------------------------


def test_tier1_bandit_neutral():
    off = repro.run(_spec(train=False))
    on = repro.run(_spec(train=False, telemetry=True))
    _assert_same_decisions(off, on)
    assert off.tier == on.tier == 1
    # bandit scans carry no taps: telemetry stays None, never fake data
    assert on.telemetry is None


def test_tier2_host_loop_neutral():
    off = repro.run(_spec(policy="CUCB"))
    on = repro.run(_spec(policy="CUCB", telemetry=True))
    _assert_same_decisions(off, on)
    assert off.tier == on.tier == 2
    assert on.telemetry is None


def test_tier3_fused_neutral(fused_off, fused_on):
    _assert_same_decisions(fused_off, fused_on)
    assert fused_off.tier == fused_on.tier == 3
    assert fused_off.telemetry is None
    assert fused_on.telemetry is not None


def test_tier4_device_env_neutral():
    off = repro.run(_spec(backend="device"))
    on = repro.run(_spec(backend="device", telemetry=True))
    _assert_same_decisions(off, on)
    assert off.tier == on.tier == 4
    assert on.telemetry is not None


# -- tap correctness vs the host oracle ---------------------------------------


def test_taps_match_host_oracle(fused_on):
    """Per-round selected/arrived/deadline-miss counts accumulated on
    device must equal the host-side float64 oracle computed from the
    run's own outputs."""
    t = fused_on.telemetry
    series, totals = t["series"], t["totals"]
    sel_oracle = (np.asarray(fused_on.selections) >= 0).sum(axis=2)
    np.testing.assert_array_equal(series["selected"],
                                  sel_oracle.astype(np.float64))
    np.testing.assert_array_equal(series["arrived"],
                                  np.asarray(fused_on.participants,
                                             np.float64))
    # fault-free run: every selected client either arrives or misses
    np.testing.assert_array_equal(
        series["deadline_miss"], series["selected"] - series["arrived"])
    # carried totals == series sums (accumulator threaded across blocks)
    for key in ("selected", "arrived", "deadline_miss"):
        np.testing.assert_allclose(totals[key], series[key].sum(axis=1))
    np.testing.assert_allclose(totals["explored"],
                               np.asarray(fused_on.explored).sum(axis=1))
    assert t["summary"]["rounds"] == HORIZON
    assert t["summary"]["participants_per_round"] == pytest.approx(
        np.asarray(fused_on.participants).mean())
    # UCB width is a confidence radius in [0, 1] that shrinks over time
    width = series["ucb_width"].mean(axis=0)
    assert np.all(width >= 0) and np.all(width <= 1)
    assert width[-1] < width[0]


def test_aggregator_adjusted_counts_trims():
    res = repro.run(_spec(telemetry=True, aggregator="trimmed_mean"))
    adj = res.telemetry["series"]["agg_adjusted"]
    assert np.all(adj >= 0)
    assert adj.sum() > 0            # cohorts of >= 3 slots get trimmed
    assert res.telemetry["summary"]["mean_agg_adjusted"] > 0


# -- tracing + report ----------------------------------------------------------


def test_trace_and_report(tmp_path, fused_off):
    trace = str(tmp_path / "run.jsonl")
    pft = str(tmp_path / "run.trace.json")
    res = repro.run(_spec(telemetry=True, trace=trace, perfetto=pft))
    _assert_same_decisions(fused_off, res)      # tracing never perturbs
    recs = [json.loads(ln) for ln in open(trace)]
    names = {r["name"] for r in recs}
    assert {"run.resolve", "run.dispatch", "env.realize", "train.prepare",
            "fused_block", "telemetry"} <= names
    blocks = [r for r in recs if r["name"] == "fused_block"]
    assert len(blocks) == HORIZON // EVERY
    for b in blocks:
        assert b["dur_us"] >= 0 and {"compiled", "factory_hit",
                                     "dispatch_us",
                                     "execute_us"} <= set(b)
    report = render_report(trace)
    assert "## Phase times" in report
    assert "## Fused blocks" in report
    assert "fused_block" in report
    assert "## Telemetry — COCS" in report
    assert "participation / round" in report
    # perfetto export written on tracer close, loadable trace_event JSON
    with open(pft) as f:
        pf = json.load(f)
    assert len(pf["traceEvents"]) == len(recs) - 1      # minus header
    assert export_perfetto(trace, str(tmp_path / "again.json")) > 0


def test_report_rejects_non_trace_input(tmp_path):
    """A ledger/arbitrary file is refused with a named error, not a
    raw traceback (the CLI renders it as `error: ...`, exit 2)."""
    p = tmp_path / "ledger.json"
    p.write_text('[{"name": "x"}]\n')
    with pytest.raises(ValueError, match="not a repro JSONL trace"):
        render_report(str(p))
    with pytest.raises(ValueError, match="not a repro JSONL trace"):
        export_perfetto(str(p), str(tmp_path / "out.json"))


def test_health_events_reach_the_trace(tmp_path):
    """PR 8's carry-guard findings must appear in the JSONL stream, not
    only in RunResult.health."""
    trace = str(tmp_path / "bad.jsonl")
    res = repro.run(_spec(horizon=8, lr=float("nan"), health="record",
                          trace=trace))
    assert len(res.health["events"]) == 2
    health = [json.loads(ln) for ln in open(trace)
              if json.loads(ln).get("name") == "health"]
    assert len(health) == 2
    assert health[0]["round_end"] == 4
    assert any("edge" in leaf for leaf in health[0]["bad"])
    assert "Health events" in render_report(trace)


# -- checkpoint/resume interplay ----------------------------------------------


def test_kill_resume_with_telemetry_bitwise(tmp_path, fused_on):
    """A killed telemetry run resumes bitwise — including the telemetry
    series/totals, whose accumulator rides the checkpointed carry."""
    ck = str(tmp_path / "ck")
    spec = _spec(telemetry=True)
    env = build_env(spec.env)
    pol = build_policy(spec.policy, env.cfg, spec.horizon)
    with pytest.raises(SimulatedKill):
        sweep_experiments({spec.policy.name: pol}, env, list(spec.seeds),
                          spec.horizon, eval_every=EVERY,
                          checkpoint_dir=ck, telemetry=True,
                          stop_after_blocks=2)
    resumed = repro.run(_spec(telemetry=True, checkpoint_dir=ck,
                              resume=True))
    _assert_same_decisions(fused_on, resumed)
    for key, val in fused_on.telemetry["series"].items():
        np.testing.assert_array_equal(val, resumed.telemetry["series"][key],
                                      err_msg=key)
    for key, val in fused_on.telemetry["totals"].items():
        np.testing.assert_allclose(val, resumed.telemetry["totals"][key],
                                   err_msg=key)


def test_resume_refuses_cross_telemetry_mode(tmp_path):
    """A telemetry-on checkpoint is a different run shape than the
    telemetry-off one — resuming across modes must be refused."""
    ck = str(tmp_path / "ck")
    spec = _spec(telemetry=True)
    env = build_env(spec.env)
    pol = build_policy(spec.policy, env.cfg, spec.horizon)
    with pytest.raises(SimulatedKill):
        sweep_experiments({spec.policy.name: pol}, env, list(spec.seeds),
                          spec.horizon, eval_every=EVERY,
                          checkpoint_dir=ck, telemetry=True,
                          stop_after_blocks=1)
    with pytest.raises(ValueError, match="different run"):
        repro.run(_spec(checkpoint_dir=ck, resume=True))


# -- ObsSpec -------------------------------------------------------------------


def test_obsspec_round_trip():
    spec = _spec(telemetry=True, trace="/tmp/x.jsonl")
    back = ExperimentSpec.from_dict(spec.to_dict())
    assert back == spec
    assert back.obs.telemetry is True and back.obs.trace == "/tmp/x.jsonl"
    # default obs round-trips too (and stays disabled)
    plain = _spec()
    assert ExperimentSpec.from_dict(plain.to_dict()).obs == ObsSpec()
    assert not ObsSpec().enabled and spec.obs.enabled


def test_obsspec_rejects_perfetto_without_trace():
    with pytest.raises(ValueError, match="perfetto"):
        ObsSpec(perfetto="/tmp/out.json")


def test_trial_record_telemetry_rides_outside_metrics():
    from repro.trials.metrics import TrialRecord, record_from_entry
    rec = TrialRecord(suite="s", policy="COCS", coord=(),
                      cum_utility=1.0, cum_utility_seeds=(1.0,),
                      participation=2.0,
                      telemetry={"deadline_miss_rate": 0.25})
    entry = rec.to_entry()
    assert entry["telemetry"] == {"deadline_miss_rate": 0.25}
    assert "deadline_miss_rate" not in entry["metrics"]
    assert record_from_entry(entry).telemetry == rec.telemetry


# -- logging setup -------------------------------------------------------------


def test_logging_default_is_print_compatible(capfd):
    log = logging_setup.setup()
    log.info("name,123.4,derived=ok")
    out, err = capfd.readouterr()
    assert out == "name,123.4,derived=ok\n"
    assert err == ""


def test_progress_lines_go_to_stderr(capfd):
    logging_setup.setup()
    logging_setup.get_logger("repro.progress").info("[suite] 1/4 COCS")
    out, err = capfd.readouterr()
    assert out == ""
    assert "[suite] 1/4 COCS" in err


def test_quiet_drops_info_keeps_warnings(capfd):
    try:
        log = logging_setup.setup(quiet=True)
        log.info("hidden")
        log.warning("shown")
        out, _ = capfd.readouterr()
        assert "hidden" not in out and "shown" in out
    finally:
        logging_setup.setup()       # restore defaults for other tests


def test_env_var_zero_code_capture(tmp_path, monkeypatch):
    """REPRO_TRACE activates the global tracer without any code change
    (the CI benchmark step's capture path)."""
    import repro.obs.trace as tr
    trace = str(tmp_path / "env.jsonl")
    monkeypatch.setattr(tr, "_TRACER", None)
    monkeypatch.setattr(tr, "_ENV_CHECKED", False)
    monkeypatch.setenv("REPRO_TRACE", trace)
    try:
        assert tr.active() is not None
        with tr.span("unit", k=1):
            pass
        tr.event("mark", n=2)
        tr._close_global()
    finally:
        monkeypatch.setattr(tr, "_ENV_CHECKED", True)
    recs = [json.loads(ln) for ln in open(trace)]
    assert [r["name"] for r in recs] == ["repro-trace/v1", "unit", "mark"]
    assert recs[1]["k"] == 1 and recs[2]["n"] == 2
    assert os.path.getsize(trace) > 0


# -- stage scopes on the device ------------------------------------------------

STAGES = {"env", "select", "walk", "train", "aggregate", "eval"}


def _scoped_ops(jitted, *args):
    """The ``hfl.*`` scopes the lowered module's debug locations name, and
    the ``op_name`` of every op of the compiled program: the path a device
    profile reads for each op event."""
    import re
    lowered = jitted.lower(*args)
    named = set(re.findall(r"hfl\.(\w+)", lowered.as_text(debug_info=True)))
    ops = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    return named, ops


def _assert_walk_in_select(ops):
    """Every while loop under ``hfl.walk`` sits inside ``hfl.select``
    (ops of the program itself, with their full path; reducer bodies
    keep relative names)."""
    import re
    whiles = [o for o in ops if o.startswith("jit(")
              and re.search(r"hfl\.walk\)*/while$", o)]
    assert whiles, "no while loop under hfl.walk"
    for o in whiles:
        assert re.search(r"hfl\.select\b.*hfl\.walk", o), o


def _captured_block(monkeypatch, env):
    """The tier-4 fused block and its arguments, as ``repro.run`` makes
    them at a tiny size (the block itself is not run)."""
    from repro.experiment import sweep
    got = []

    class Captured(Exception):
        pass

    def capture(factory, make_args, *rest):
        got.append(make_args())
        raise Captured

    monkeypatch.setattr(sweep, "_traced_block", capture)
    spec = ExperimentSpec(env=env, policy=PolicySpec("cocs"),
                          train=TrainSpec(model="logreg", batch_size=16),
                          eval=EvalSpec(eval_every=2), horizon=2,
                          seeds=SEEDS)
    with pytest.raises(Captured):
        repro.run(spec)
    return got[0]


TINY_METRO = {"num_clients": 64, "num_edge_servers": 4, "h_t": 3}


@pytest.mark.parametrize("env", [
    EnvSpec("paper", backend="device"),
    EnvSpec("metropolis-1k", config="mnist-metropolis-1k",
            overrides=TINY_METRO, true_p="analytic")],
    ids=["paper", "metropolis"])
def test_stage_scopes_cover_the_fused_block(monkeypatch, env):
    fn, args = _captured_block(monkeypatch, env)
    named, ops = _scoped_ops(fn, *args)
    assert STAGES <= named
    _assert_walk_in_select(ops)
    for stage in STAGES:
        assert any(f"hfl.{stage}" in o for o in ops), stage


def test_stage_scopes_cover_the_bandit_scan():
    """Tier 1 (and the training tiers' pre-scan): env and selection only."""
    import jax.numpy as jnp
    from repro.policies.engine import stack_states
    from repro.sim.engine import _compiled_bandit
    spec = ExperimentSpec(env=EnvSpec("paper", backend="device"),
                          policy=PolicySpec("cocs"), horizon=3, seeds=SEEDS)
    env = build_env(spec.env)
    pol = build_policy(spec.policy, env.cfg, spec.horizon)
    fn = _compiled_bandit(pol, env.spec, spec.horizon)
    named, ops = _scoped_ops(fn, jnp.asarray(np.array(SEEDS, np.uint32)),
                             stack_states(pol, list(SEEDS)))
    assert named == {"env", "select", "walk"}
    _assert_walk_in_select(ops)


def test_stage_scopes_cover_the_sharded_block():
    import jax.numpy as jnp
    from repro import api
    from repro.experiment.sweep import prepare_training
    from repro.mesh.engine import ShardDims, sharded_block_device
    from repro.policies.engine import stack_states
    from repro.sim.core import init_statics_multi
    spec = ExperimentSpec(
        env=EnvSpec("metropolis-1k", config="mnist-metropolis-1k",
                    overrides=TINY_METRO, true_p="analytic"),
        policy=PolicySpec("cocs"), train=TrainSpec(batch_size=16),
        eval=EvalSpec(eval_every=2), horizon=2, seeds=SEEDS,
        shard=api.ShardSpec(clients=1))
    env = build_env(spec.env)
    pol = build_policy(spec.policy, env.cfg, spec.horizon)
    setup = prepare_training(env.cfg, "logreg", 16, 2, None, list(SEEDS))
    statics = init_statics_multi(env.spec, list(SEEDS))
    n = env.cfg.num_clients
    fn = sharded_block_device(
        pol, setup.spec, 6, setup.batch, setup.loss_fn, setup.logits_fn,
        env.spec, ShardDims(num_clients=n, n_local=n, seed_shards=1,
                            client_shards=1))
    named, ops = _scoped_ops(
        fn, setup.stacked.x, setup.stacked.y, setup.stacked.sizes,
        setup.base_keys, stack_states(pol, list(SEEDS)), setup.edge_seed,
        statics.pos0, jnp.asarray(np.array(SEEDS, np.uint32)), statics,
        jnp.arange(0, 2, dtype=jnp.int32), setup.test_x, setup.test_y)
    assert STAGES <= named
    _assert_walk_in_select(ops)


@pytest.mark.parametrize("solver", ["greedy_assign", "flgreedy_assign",
                                    "budgeted_topk", "flgreedy_topk"])
def test_every_budget_walk_is_scoped(solver):
    """All three walks: the solvers' fallback loops and the sorted
    ``greedy_walk`` / ``flgreedy_walk`` behind the kernel path."""
    import jax.numpy as jnp
    from repro.kernels.budgeted_topk import ops as topk
    from repro.policies import solvers
    fn = getattr(solvers, solver, None) or getattr(topk, solver)
    rng = np.random.default_rng(0)
    n, m = 12, 3
    args = (jnp.asarray(rng.random((n, m)), jnp.float32),
            jnp.asarray(rng.random(n) + 0.5, jnp.float32),
            jnp.full((m,), 2.0, jnp.float32),
            jnp.asarray(rng.random((n, m)) > 0.3))
    named, ops = _scoped_ops(fn, *args)
    assert named == {"walk"}
    assert any(o.endswith("hfl.walk/while") for o in ops)


# -- spans on the profiler's clock, ids, records kept until close -------------


def _host_annotations(prof_dir):
    import glob
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return {e.name: e for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU" for line in p.lines
            for e in line.events if e.name.startswith("repro:")}


def test_spans_sit_on_the_profiler_clock_with_ids(tmp_path):
    import jax
    from repro.obs import trace as tr
    log = str(tmp_path / "ids.jsonl")
    prof = str(tmp_path / "prof")
    jax.profiler.start_trace(prof)
    try:
        with tr.trace_to(log):
            with tr.run_tracing(ObsSpec()):
                with tr.span("outer"):
                    with tr.span("inner"):
                        tr.event("mark")
            with tr.run_tracing(ObsSpec()):
                with tr.span("second"):
                    pass
        with tr.span("untraced"):       # no JSONL tracer: annotation only
            pass
    finally:
        jax.profiler.stop_trace()
    recs = {r["name"]: r for r in map(json.loads, open(log))}
    outer, inner, second = recs["outer"], recs["inner"], recs["second"]
    assert outer["parent"] is None and inner["parent"] == outer["id"]
    assert inner["run"] == outer["run"] is not None
    assert second["run"] not in (None, outer["run"])
    assert recs["mark"]["parent"] == inner["id"]
    assert recs["mark"]["run"] == outer["run"]

    ann = _host_annotations(prof)
    assert {"repro:outer", "repro:inner", "repro:second",
            "repro:untraced"} <= set(ann)
    o, i = ann["repro:outer"], ann["repro:inner"]
    assert o.start_ns <= i.start_ns
    assert i.start_ns + i.duration_ns <= o.start_ns + o.duration_ns
    so, si = dict(o.stats), dict(i.stats)
    assert (so["id"], so["run"]) == (outer["id"], outer["run"])
    assert (si["id"], si["parent"], si["run"]) == (
        inner["id"], outer["id"], outer["run"])
    assert "parent" not in so


def test_tracer_writes_on_close_only(tmp_path):
    from repro.obs.trace import Tracer
    path = tmp_path / "t.jsonl"
    t = Tracer(str(path))
    t.event("a", k=1)
    t.span_record("s", 0, 5, {"x": np.float32(2.5)})
    assert not path.exists() or path.stat().st_size == 0
    t.close()
    recs = [json.loads(ln) for ln in open(path)]
    assert [r["name"] for r in recs] == ["repro-trace/v1", "a", "s"]
    assert recs[1]["k"] == 1 and recs[2]["x"] == 2.5
    t.close()                           # a second close writes nothing
    assert len(open(path).readlines()) == 3
