"""Reduction of a ``jax.profiler`` trace to the numbers the metrics read.

The trace holds the device's op events and the harness's own host
annotations (one ``bench:experiment <i>`` per experiment of the traced
stretch). Everything here works on plain ``(name, start_ns, dur_ns)``
tuples, so the tests feed it small synthetic traces.

* busy time: the union of the device op intervals inside the window;
* idle gaps: the holes of that union inside the window, each labelled by
  the experiment annotation and the ``repro.obs`` span in progress;
* op totals: device time per op name, for the breakdown and the kernel
  rooflines (a kernel module says which names are its own).
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

ANNOTATION = "bench:experiment"
# the device line that holds one event per executed XLA op
OPS_LINE = "XLA Ops"

Event = Tuple[str, int, int]          # (name, start_ns, dur_ns)


class Trace(NamedTuple):
    ops: List[Event]                  # device op events of one chip
    annotations: List[Event]          # the harness's experiment spans
    chips: int                        # device planes found

    @property
    def window(self) -> Tuple[int, int]:
        """From the first traced experiment's start to the last's end."""
        if not self.annotations:
            return (0, 0)
        return (min(a[1] for a in self.annotations),
                max(a[1] + a[2] for a in self.annotations))


def load(directory: str) -> Optional[Trace]:
    """The newest ``.xplane.pb`` under ``directory``; None if none."""
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(paths[-1])
    per_chip, notes = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "Core" not in plane.name:
            ops = [(e.name, int(e.start_ns), int(e.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                per_chip.append(ops)
        elif plane.name.startswith("/host:"):
            notes += [(e.name, int(e.start_ns), int(e.duration_ns))
                      for line in plane.lines for e in line.events
                      if e.name.startswith(ANNOTATION)]
    return Trace(ops=per_chip[0] if per_chip else [], annotations=notes,
                 chips=len(per_chip))


def clip(intervals: Sequence[Tuple[int, int]], window: Tuple[int, int]):
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: Trace) -> int:
    iv = clip([(s, s + d) for _, s, d in trace.ops], trace.window)
    return sum(e - s for s, e in union(iv))


def gaps(trace: Trace) -> List[Tuple[int, int]]:
    """Idle holes of the busy union inside the window."""
    lo, hi = trace.window
    merged = union(clip([(s, s + d) for _, s, d in trace.ops], trace.window))
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def self_times(ops: Sequence[Event]) -> List[Event]:
    """Each op with the time of the ops nested in it taken out (a loop's
    event holds the events of its body)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [d for _, _, d in ops]
    stack: List[int] = []
    for i in order:
        s, e = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            own[stack[-1]] -= ops[i][2]
        stack.append(i)
    return [(n, s, own[i]) for i, (n, s, _) in enumerate(ops)]


def op_totals(trace: Trace) -> Dict[str, float]:
    """Device seconds per op name inside the window, nested ops' time
    counted once, with the innermost op."""
    lo, hi = trace.window
    tot: Dict[str, float] = defaultdict(float)
    for name, s, d in self_times(trace.ops):
        if lo <= s < hi:
            tot[name] += d / 1e9
    return dict(tot)


def label(at_ns: int, trace: Trace, spans: Sequence[Event]) -> str:
    """What the host was doing at ``at_ns``: the experiment annotation
    and the innermost (shortest) host span covering it."""
    exp = [a[0][len(ANNOTATION):].strip() for a in trace.annotations
           if a[1] <= at_ns < a[1] + a[2]]
    inner = [s for s in spans if s[1] <= at_ns < s[1] + s[2]]
    what = min(inner, key=lambda s: s[2])[0] if inner else "outside spans"
    return (f"experiment {exp[0]}: " if exp else "") + what


def breakdown(trace: Trace, spans: Sequence[Event], k: int = 10):
    ops = sorted(op_totals(trace).items(), key=lambda kv: -kv[1])[:k]
    holes = sorted(gaps(trace), key=lambda g: g[0] - g[1])[:k]
    # an op's name is its HLO line; its head names it well enough
    return {"device_ops": [[n[:120], t] for n, t in ops],
            "idle_gaps": [[label((a + b) // 2, trace, spans), (b - a) / 1e9]
                          for a, b in holes]}


def spans_on_trace_clock(records: Sequence[dict], anchors: Sequence[Tuple[int, int]]
                         ) -> List[Event]:
    """``repro.obs`` span records (microseconds of ``perf_counter_ns``)
    moved onto the trace clock. ``anchors`` pairs each traced
    experiment's annotation start on the trace clock with the harness's
    ``perf_counter_ns`` reading at the same moment; their median offset
    is used."""
    if not anchors:
        return []
    offs = sorted(t - p for t, p in anchors)
    off = offs[len(offs) // 2]
    return [(r["name"], int(r["ts"]) * 1000 + off, int(r["dur_us"]) * 1000)
            for r in records if r.get("ev") == "span"]
