"""Device time by stage and idle time between programs (``bench/stages.py``),
on synthetic events and profiles, and the new metrics' silence where a
run has no trace or a program has no stage scopes."""
import pytest

from bench import cells, stages
from bench.devtrace import ANNOTATION as A, Trace
from bench.run import ROOT

WALK = "jit(block)/while/body/hfl.select/vmap(jit(greedy_assign))/hfl.walk"
NEW = ("env_device_ms", "select_device_ms", "walk_device_ms",
       "train_device_ms", "aggregate_device_ms", "eval_device_ms",
       "unscoped_device_ms", "idle_between_programs_ms")


def test_scope_is_the_innermost_stage():
    assert stages.scope(WALK + "/while") == "walk"
    assert stages.scope(WALK.replace("hfl.walk", "x") + "/gather") == "select"
    assert stages.scope("jit(block)/while/body/vmap(hfl.env)/sin") == "env"
    assert stages.scope("jit(block)/while") is None
    assert stages.scope("jit(f)/hfl.environment/sin") is None
    assert stages.scope(None) is None


def ops():
    # a scan loop [0, 100) holding env [0, 10), a select region [10, 60)
    # whose walk loop [20, 50) holds a gather [25, 45); train [60, 90);
    # an op of the next program starts past the window
    return [("while", 0, 100, None), ("fusion.1", 0, 10, "env"),
            ("fusion.2", 10, 50, "select"), ("while.3", 20, 30, "walk"),
            ("gather", 25, 20, "walk"), ("conv", 60, 30, "train"),
            ("late", 120, 10, "eval")]


def test_stage_time_is_self_time_by_innermost_scope():
    got = stages.stage_ns(ops(), (0, 110))
    assert got == {"env": 10, "select": 50, "walk": 30, "train": 30,
                   "aggregate": 0, "eval": 0, "unscoped": 10}
    # the top-level stages and the unscoped rest add up to the busy time
    top = sum(got[k] for k in ("env", "select", "train", "aggregate",
                               "eval", "unscoped"))
    assert top == 100


def test_ops_without_a_stage_take_the_one_around_or_inside_them():
    # a scan [0, 100) on no stage holds a loop [10, 60) the compiler made
    # (no stage) whose body is training, with a copy [40, 45) in it; a
    # walk loop [60, 80) whose body op kept no stage; and a copy [85, 95)
    # at the scan's own level, which stays unscoped
    ops = [("scan", 0, 100, None), ("loop", 10, 50, None),
           ("conv", 10, 20, "train"), ("copy-done", 40, 5, None),
           ("while", 60, 20, "walk"), ("copy", 65, 5, None),
           ("dus", 85, 10, None)]
    assert stages.resolve(ops) == [None, "train", "train", "train", "walk",
                                   "walk", None]
    assert stages.resolve([("loop", 0, 10, None), ("a", 0, 4, "select"),
                           ("b", 4, 4, "walk")])[0] == "select"
    got = stages.stage_ns(ops, (0, 100))
    assert got["train"] == 50 and got["walk"] == 20 and got["select"] == 20
    assert got["unscoped"] == 30


def _space(scoped=True, modules=((0, 100),)):
    """A serialized XSpace: one TPU plane with ops and modules (offset,
    duration), a host plane with the harness's experiment and a repro
    span."""
    space = stages.xspace_class()()
    dev = space.planes.add(id=1, name="/device:TPU:0")
    dev.stat_metadata[1].name = "tf_op"
    dev.stat_metadata[2].name = WALK + "/while"      # interned string
    paths = {10: "jit(block)/while",
             11: "jit(block)/while/body/vmap(hfl.env)/sin"}
    for mid, path in paths.items():
        md = dev.event_metadata[mid]
        md.name = f"op{mid}"
        if scoped:
            md.stats.add(metadata_id=1, str_value=path)
    md = dev.event_metadata[12]
    md.name = "op12"
    if scoped:
        md.stats.add(metadata_id=1, ref_value=2)
    dev.event_metadata[20].name = "jit_block(42)"
    line = dev.lines.add(id=1, name="XLA Ops", timestamp_ns=1000)
    for mid, off, dur in ((10, 0, 100), (11, 0, 10), (12, 20, 30)):
        line.events.add(metadata_id=mid, offset_ps=off * 1000,
                        duration_ps=dur * 1000)
    line = dev.lines.add(id=2, name="XLA Modules", timestamp_ns=1000)
    for off, dur in modules:
        line.events.add(metadata_id=20, offset_ps=off * 1000,
                        duration_ps=dur * 1000)
    host = space.planes.add(id=2, name="/host:CPU")
    host.event_metadata[1].name = f"{A} 0"
    host.event_metadata[2].name = "repro:fused_block_device"
    host.event_metadata[3].name = "PjitFunction(block)"
    line = host.lines.add(id=1, name="python", timestamp_ns=900)
    for mid, off, dur in ((1, 100, 200), (2, 150, 120), (3, 160, 5)):
        line.events.add(metadata_id=mid, offset_ps=off * 1000,
                        duration_ps=dur * 1000)
    return space.SerializeToString()


def test_parse_reads_the_scope_from_the_op_metadata():
    prof = stages.parse(_space())
    assert prof.ops == [("op10", 1000, 100, None), ("op11", 1000, 10, "env"),
                        ("op12", 1020, 30, "walk")]
    assert prof.modules == [("jit_block(42)", 1000, 100)]
    assert prof.host == [("repro:fused_block_device", 1050, 120)]


def _ctx(tmp_path, data, workload="metropolis-1k.bandit",
         experiments=((1000, 100), (1100, 100))):
    prof = tmp_path / "bench_out" / workload / "profile" / "plugins"
    prof.mkdir(parents=True)
    (prof / "vm.xplane.pb").write_bytes(data)

    class Ctx:
        cell = {"workload": {"name": workload}}
        root = str(tmp_path)
        # by default two traced experiments over [1000, 1200)
        trace = Trace(ops=[], annotations=[
            (f"{A} {i}", s, d) for i, (s, d) in enumerate(experiments)],
            chips=1)
    return Ctx


def test_metrics_read_a_profile_per_experiment(tmp_path):
    ctx = _ctx(tmp_path, _space())
    got = {n: cells.module(ROOT, "metrics", n).read(ctx) for n in NEW}
    per = 1e-6 / 2
    assert got["env_device_ms"] == pytest.approx(10 * per)
    assert got["walk_device_ms"] == pytest.approx(30 * per)
    assert got["select_device_ms"] == pytest.approx(30 * per)
    assert got["unscoped_device_ms"] == pytest.approx(60 * per)
    assert got["train_device_ms"] == 0.0
    assert got["idle_between_programs_ms"] == pytest.approx(100 * per)


def test_gaps_between_programs_are_clipped_to_the_window(tmp_path):
    # programs over [1000, 1015) and [1030, 1035); one experiment traced
    # over [1008, 1040): the holes inside it are [1015, 1030), [1035, 1040)
    mods = ((0, 10), (5, 10), (30, 5), (50, 10))
    ctx = _ctx(tmp_path, _space(modules=mods), experiments=((1008, 32),))
    got = cells.module(ROOT, "metrics", "idle_between_programs_ms").read(ctx)
    assert got == pytest.approx(20e-6)


def test_metrics_are_silent_without_a_trace_or_scopes(tmp_path):
    class NoTrace:
        cell = {"workload": {"name": "metropolis-1k.bandit"}}
        root = str(tmp_path)
        trace = None
    for name in NEW:
        assert cells.module(ROOT, "metrics", name).read(NoTrace) is None
    # a profile of a program without the scopes: the stage metrics say
    # nothing, the idle between programs is still read
    ctx = _ctx(tmp_path, _space(scoped=False))
    for name in NEW[:-1]:
        assert cells.module(ROOT, "metrics", name).read(ctx) is None
    assert cells.module(ROOT, "metrics", NEW[-1]).read(ctx) is not None


def test_parse_agrees_with_profile_data(tmp_path):
    """On a recorded trace the host spans land on the clock
    ``devtrace.load`` reads."""
    import jax
    import jax.numpy as jnp
    from bench import devtrace
    from repro.obs import trace as obs_trace
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(f"{A} 0"):
            with obs_trace.span("unit"):
                f(x).block_until_ready()
    tr = devtrace.load(str(tmp_path))
    prof = stages.load(str(tmp_path))
    ((_, start, dur),) = tr.annotations
    ((name, s, d),) = prof.host
    assert name == "repro:unit" and start <= s and s + d <= start + dur
    assert devtrace.label(s + d // 2, tr, prof.host) == \
        "experiment 0: repro:unit"
