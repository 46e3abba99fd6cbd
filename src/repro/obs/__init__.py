"""``repro.obs``: observability for the fused HFL engine.

Three coordinated layers (see ROADMAP "Observability"):

* host tracing   — ``obs.span``/``obs.event``/``obs.trace_to`` write a
                   JSONL event log of the run lifecycle (+ Perfetto
                   export, + opt-in ``jax.profiler`` capture); under a
                   running ``jax.profiler`` trace each span is also a
                   ``repro:<name>`` annotation on the profiler's clock,
                   and ``repro.obs.scopes`` names the ``hfl.*`` stage
                   scopes the compiled rounds carry on the device;
* device taps    — ``ObsSpec(telemetry=True)`` threads a pure metric
                   accumulator through the tier-3/4 fused scan and
                   surfaces it as ``RunResult.telemetry``;
* run profiles   — ``python -m repro.obs report run.jsonl`` renders a
                   markdown phase-time + telemetry profile; summaries
                   flow into ``repro.trials`` ledger rows.

This package's eager surface is jax-free (spec, tracer, logging) so CLI
paths stay light; ``repro.obs.telemetry`` (jax) and ``repro.obs.report``
load lazily.
"""
from repro.obs import logging_setup
from repro.obs.spec import ObsSpec
from repro.obs.trace import (Tracer, active, configure, event,
                             export_perfetto, run_tracing, span, trace_to)

_LAZY = ("telemetry", "report")


def __getattr__(name):
    if name in _LAZY:
        import importlib
        return importlib.import_module(f"repro.obs.{name}")
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")


__all__ = ["ObsSpec", "Tracer", "active", "configure", "event",
           "export_perfetto", "run_tracing", "span", "trace_to",
           "logging_setup", "telemetry", "report"]
