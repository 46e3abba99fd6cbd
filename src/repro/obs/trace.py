"""Host span tracer: a JSONL event log for the run lifecycle.

The tracer is deliberately tiny — no external deps, records kept in
memory — because it sits on the hot dispatch path of the fused engine.
Each record is one JSON object per line:

    {"ev": "span",  "name": "fused_block", "ts": ..., "dur_us": ...,
     "pid": ..., "tid": ..., "id": ..., "parent": ..., "run": ...,
     ...attrs}
    {"ev": "event", "name": "health",      "ts": ..., ...attrs}

``ts`` is microseconds from ``time.perf_counter_ns`` (monotonic; only
deltas within one log are meaningful), plus a ``wall`` ISO timestamp on
the header record for humans. ``id`` numbers the span, ``parent`` is the
id of the span it ran inside (null at the top), and ``run`` is shared by
every span and event of one ``repro.run`` call (null outside one). The
records are written when the tracer closes (``close``, the end of
``trace_to``, or process exit), not one write per record.
``export_perfetto`` renders the log as a Chrome ``trace_event`` JSON that
chrome://tracing and ui.perfetto.dev load directly.

While a ``jax.profiler`` trace is running, every span also opens a
``jax.profiler.TraceAnnotation`` named ``repro:<name>`` carrying the same
three ids, so the span sits on the profiler's host plane, on the clock
of the device's op events. This happens whether or not a JSONL tracer is
active, and only once ``jax`` is imported: the tracer never imports it.

Activation is explicit (``configure``/``trace_to``/``run_tracing``) or
via environment for zero-code capture of existing entry points:

    REPRO_TRACE=run.jsonl REPRO_TRACE_PERFETTO=run.trace.json \
        python benchmarks/run.py ...

Instrumentation sites call ``span``/``event`` unconditionally; when no
tracer is active and no profiler runs they cost two flag checks and no
allocation.
"""
from __future__ import annotations

import atexit
import contextlib
import contextvars
import datetime
import itertools
import json
import os
import sys
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

_SCHEMA = "repro-trace/v1"
ANNOTATION_PREFIX = "repro:"

_IDS = itertools.count(1)
# the id of the innermost open span, and of the repro.run call
_PARENT: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "repro_obs_parent", default=None)
_RUN: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "repro_obs_run", default=None)


def now_us() -> int:
    """Monotonic microsecond clock (the timestamps in trace records)."""
    return time.perf_counter_ns() // 1000


_now_us = now_us


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` while a profiler trace runs;
    None when none runs or ``jax`` was never imported."""
    ann = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)
    return ann if ann is not None and ann.is_enabled() else None


class Tracer:
    """Keeps span/event records in memory and appends them to a JSONL
    file on ``close`` (also called at process exit)."""

    def __init__(self, path: str, perfetto: Optional[str] = None):
        self.path = path
        self.perfetto = perfetto
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        self._lines: List[str] = []
        self._closed = False
        self._lock = threading.Lock()
        self._pid = os.getpid()
        self._add({"ev": "begin", "name": _SCHEMA, "ts": _now_us(),
                   "wall": datetime.datetime.now(datetime.timezone.utc)
                   .isoformat()})
        atexit.register(self.close)

    def _add(self, rec: Dict[str, Any]) -> None:
        # list.append is atomic: no lock and no I/O on the dispatch path
        self._lines.append(json.dumps(rec, default=_jsonable))

    def event(self, name: str, **attrs: Any) -> None:
        self._add({"ev": "event", "name": name, "ts": _now_us(),
                   "pid": self._pid, "tid": threading.get_ident() & 0xFFFF,
                   "parent": _PARENT.get(), "run": _RUN.get(), **attrs})

    def span_record(self, name: str, ts: int, dur_us: int,
                    attrs: Dict[str, Any],
                    ids: Optional[Dict[str, Any]] = None) -> None:
        self._add({"ev": "span", "name": name, "ts": ts,
                   "dur_us": dur_us, "pid": self._pid,
                   "tid": threading.get_ident() & 0xFFFF,
                   **(ids or {}), **attrs})

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            lines, self._lines = self._lines, []
        atexit.unregister(self.close)
        with open(self.path, "a", encoding="utf-8") as f:
            f.write("".join(line + "\n" for line in lines))
        if self.perfetto:
            export_perfetto(self.path, self.perfetto)


def _jsonable(x: Any) -> Any:
    # numpy / jax scalars and arrays reach the tracer from attrs; keep
    # the hot path free of imports by duck-typing them here.
    if hasattr(x, "item") and getattr(x, "ndim", None) in (0, None):
        return x.item()
    if hasattr(x, "tolist"):
        return x.tolist()
    return str(x)


# -- global activation -------------------------------------------------------

_TRACER: Optional[Tracer] = None
_ENV_CHECKED = False


def active() -> Optional[Tracer]:
    """The current tracer, if any. First call honors REPRO_TRACE."""
    global _TRACER, _ENV_CHECKED
    if _TRACER is None and not _ENV_CHECKED:
        _ENV_CHECKED = True
        path = os.environ.get("REPRO_TRACE")
        if path:
            _TRACER = Tracer(path,
                             os.environ.get("REPRO_TRACE_PERFETTO") or None)
    return _TRACER


def _close_global() -> None:
    global _TRACER
    if _TRACER is not None:
        _TRACER.close()
        _TRACER = None


def configure(path: Optional[str],
              perfetto: Optional[str] = None) -> Optional[Tracer]:
    """Install (or, with ``path=None``, remove) the global tracer."""
    global _TRACER
    _close_global()
    if path is not None:
        _TRACER = Tracer(path, perfetto)
    return _TRACER


@contextlib.contextmanager
def trace_to(path: str, perfetto: Optional[str] = None) -> Iterator[Tracer]:
    """Trace the enclosed block to ``path``, restoring the previous
    tracer afterwards."""
    global _TRACER
    prev = _TRACER
    _TRACER = Tracer(path, perfetto)
    try:
        yield _TRACER
    finally:
        _TRACER.close()
        _TRACER = prev


@contextlib.contextmanager
def span(name: str, **attrs: Any) -> Iterator[Dict[str, Any]]:
    """Time the enclosed block. Yields the attrs dict so the body can
    attach results (e.g. compile hit/miss) before the record is
    written. While a ``jax.profiler`` trace runs, the block is also a
    ``repro:<name>`` annotation on the profiler's host plane. No-op (and
    no allocation beyond the dict) when neither is on."""
    tr = active()
    ann = _profiler_annotation()
    if tr is None and ann is None:
        yield attrs
        return
    ids = {"id": next(_IDS), "parent": _PARENT.get(), "run": _RUN.get()}
    token = _PARENT.set(ids["id"])
    mark = (ann(ANNOTATION_PREFIX + name,
                **{k: v for k, v in ids.items() if v is not None})
            if ann is not None else contextlib.nullcontext())
    t0 = _now_us()
    try:
        with mark:
            yield attrs
    finally:
        _PARENT.reset(token)
        if tr is not None:
            tr.span_record(name, t0, _now_us() - t0, attrs, ids)


def event(name: str, **attrs: Any) -> None:
    """Emit an instant event. No-op when no tracer is active."""
    tr = active()
    if tr is not None:
        tr.event(name, **attrs)


@contextlib.contextmanager
def run_tracing(obs_spec) -> Iterator[None]:
    """Scope a run's tracing to its ObsSpec: JSONL trace, optional
    Perfetto export on close, optional jax.profiler capture. Every span
    and event inside carries one fresh ``run`` id."""
    run_token = _RUN.set(next(_IDS))
    prof = None
    try:
        if getattr(obs_spec, "jax_profiler", None):
            import jax
            capture = jax.profiler.trace(obs_spec.jax_profiler)
            capture.__enter__()
            prof = capture
        if getattr(obs_spec, "trace", None):
            with trace_to(obs_spec.trace, obs_spec.perfetto):
                yield
        else:
            yield
    finally:
        if prof is not None:
            prof.__exit__(None, None, None)
        _RUN.reset(run_token)


# -- Perfetto / Chrome trace_event export -------------------------------------


def export_perfetto(jsonl_path: str, out_path: str) -> int:
    """Render a repro JSONL trace as Chrome ``trace_event`` JSON.
    Returns the number of trace events written."""
    events = []
    with open(jsonl_path, encoding="utf-8") as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(
                    f"{jsonl_path}:{lineno}: not a repro JSONL trace "
                    f"(expected one JSON object per line: {e})") from e
            if not isinstance(rec, dict):
                raise ValueError(
                    f"{jsonl_path}:{lineno}: not a repro JSONL trace "
                    f"(line decodes to {type(rec).__name__})")
            ev = rec.get("ev")
            common = {"name": rec.get("name", "?"),
                      "pid": rec.get("pid", 0), "tid": rec.get("tid", 0),
                      "ts": rec.get("ts", 0)}
            args = {k: v for k, v in rec.items()
                    if k not in ("ev", "name", "ts", "dur_us", "pid", "tid")}
            if ev == "span":
                events.append({**common, "ph": "X",
                               "dur": rec.get("dur_us", 0), "args": args})
            elif ev == "event":
                events.append({**common, "ph": "i", "s": "t", "args": args})
    d = os.path.dirname(os.path.abspath(out_path))
    os.makedirs(d, exist_ok=True)
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms"}, f)
    return len(events)


__all__ = ["Tracer", "active", "configure", "trace_to", "span", "event",
           "run_tracing", "export_perfetto", "now_us"]
