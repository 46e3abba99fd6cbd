"""The reduction from a profiler trace to busy time, idle gaps, op totals
and kernel matches, on small synthetic traces and one recorded one."""
import jax
import jax.numpy as jnp
import pytest

from bench import devtrace
from bench.devtrace import Trace

A = devtrace.ANNOTATION


def small():
    # ops: [0,10) and [5,15) overlap; [30,35); one op past the window
    ops = [("fusion.1", 0, 10), ("fusion.2", 5, 10), ("k", 30, 5),
           ("late", 50, 10)]
    notes = [(f"{A} 0", 0, 20), (f"{A} 1", 20, 20)]
    return Trace(ops=ops, annotations=notes, chips=1)


def test_busy_union_and_idle_gaps():
    tr = small()
    assert tr.window == (0, 40)
    assert devtrace.union([(5, 15), (0, 10), (30, 35)]) == [(0, 15), (30, 35)]
    assert devtrace.busy_ns(tr) == 20
    assert devtrace.gaps(tr) == [(15, 30), (35, 40)]


def test_idle_share_metric():
    from bench import cells
    from bench.run import ROOT
    mod = cells.module(ROOT, "metrics", "device_idle_share")

    class Ctx:
        trace = small()
    assert mod.read(Ctx) == pytest.approx(50.0)
    Ctx.trace = Trace(ops=[], annotations=[], chips=0)
    assert mod.read(Ctx) is None


def test_op_totals_in_window():
    tot = devtrace.op_totals(small())
    assert tot == pytest.approx({"fusion.1": 10e-9, "fusion.2": 10e-9,
                                 "k": 5e-9})


def test_nested_ops_count_once():
    # a loop [0, 100) holding two body ops; a separate op after it
    ops = [("while", 0, 100), ("body.a", 10, 30), ("body.b", 50, 20),
           ("after", 120, 10)]
    assert devtrace.self_times(ops) == [("while", 0, 50), ("body.a", 10, 30),
                                        ("body.b", 50, 20), ("after", 120, 10)]


def test_gap_labels_name_experiment_and_span():
    tr = small()
    spans = [("run.resolve", 14, 4), ("run.dispatch", 10, 30)]
    b = devtrace.breakdown(tr, spans)
    assert b["idle_gaps"][0] == ["experiment 1: run.dispatch", 15e-9]
    assert b["idle_gaps"][1][0] == "experiment 1: run.dispatch"
    assert devtrace.label(16, tr, spans) == "experiment 0: run.resolve"
    assert b["device_ops"][0][0] in ("fusion.1", "fusion.2")


def test_spans_move_onto_the_trace_clock():
    recs = [{"ev": "span", "name": "x", "ts": 10, "dur_us": 2},
            {"ev": "event", "name": "y", "ts": 11}]
    # trace clock = perf_counter_ns + 5000 at both anchors
    out = devtrace.spans_on_trace_clock(recs, [(5000, 0), (15000, 10000)])
    assert out == [("x", 15000, 2000)]


def test_kernel_events_matched_by_name():
    from bench import roofline
    tr = Trace(ops=[("context_pairwise_kernel.3", 0, 4),
                    ("fusion", 4, 4), ("density_sort_kernel", 8, 2)],
               annotations=[(f"{A} 0", 0, 10)], chips=1)
    got = roofline.events(tr, ("context_pairwise",))
    assert [e[0] for e in got] == ["context_pairwise_kernel.3"]


def test_recorded_trace_loads_annotations(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        for i in range(2):
            with jax.profiler.TraceAnnotation(f"{A} {i}"):
                f(x).block_until_ready()
    tr = devtrace.load(str(tmp_path))
    assert [a[0] for a in sorted(tr.annotations, key=lambda a: a[1])] == \
        [f"{A} 0", f"{A} 1"]
    lo, hi = tr.window
    assert hi > lo
    assert tr.chips == 0 and tr.ops == []      # no TPU plane on the CPU
    assert devtrace.load(str(tmp_path / "none")) is None
