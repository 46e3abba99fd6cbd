"""Device time by HFL stage, read from the op metadata in a profile.

The program marks the stages of a round with ``jax.named_scope``
(``repro.obs.scopes``): ``hfl.env``, ``hfl.select`` with ``hfl.walk``
inside it, ``hfl.train``, ``hfl.aggregate`` and ``hfl.eval``. The names
ride each op's HLO ``op_name``, and the profiler gives every ``XLA Ops``
event of the device plane that path in the stat ``tf_op`` of the
event's metadata. A fusion carries the metadata of its root op, so it
counts for its root op's stage. ``jax.profiler.ProfileData`` hands out
the stats of events but not those of their metadata, so this module
reads the ``.xplane.pb`` itself, with the protobuf runtime and a schema
of the few messages it needs (``xspace_class``).

* stage time: the self time of each op event inside the window (a
  loop's event less the events of its body, ``devtrace.self_times``),
  summed by the innermost ``hfl.*`` scope on its path. An op with no
  stage on its path (the copies and loops the compiler makes keep no
  ``op_name``, or take that of the loop around them) takes the stage of
  the op event it runs inside, else the one stage the staged ops nested
  in it agree on (``resolve``). ``select`` counts the walk as well; ops
  still on no stage are ``unscoped``. So env, select, train, aggregate,
  eval and unscoped add up to the busy time.
* idle between programs: the holes, inside the window, of the union of
  the ``XLA Modules`` events (one per program the device executes;
  ``devtrace.gaps`` over them). The rest of the idle time lies inside a
  program.

    python -m bench.stages bench_out/<workload>/profile

prints both for a recorded profile, with its largest idle gaps and the
``repro:`` host span in progress at each.
"""
from __future__ import annotations

import functools
import glob
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Tuple

from bench import devtrace

STAGES = ("env", "select", "walk", "train", "aggregate", "eval")
SCOPE = re.compile(r"\bhfl\.(" + "|".join(STAGES) + r")\b")
SCOPE_STAT = "tf_op"            # the op's HLO op_name on the device plane
MODULES_LINE = "XLA Modules"    # one event per program execution
HOST_PREFIX = "repro:"           # the program's spans on the host plane

Event = devtrace.Event


class Profile(NamedTuple):
    ops: List[Tuple[str, int, int, Optional[str]]]  # (name, start, dur, scope)
    modules: List[Event]                            # program executions
    host: List[Event]                               # repro: spans


def scope(path: str) -> Optional[str]:
    """The innermost ``hfl.*`` stage on an op's name path, or None."""
    found = SCOPE.findall(path or "")
    return found[-1] if found else None


@functools.lru_cache(maxsize=1)
def xspace_class():
    """The ``XSpace`` message of the profiler's xplane.proto, built from
    its field numbers: the planes, lines, events, stats and the two
    metadata maps, nothing else."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def message(parent, name, fields, oneof=None):
        m = parent.add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof)
        for fname, num, typ, label, ref, in_oneof in fields:
            f = m.field.add(name=fname, number=num, type=typ, label=label)
            if ref:
                f.type_name = ".bench_xplane." + ref
            if in_oneof:
                f.oneof_index = 0
        return m

    i64, u64, s, msg = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING, \
        F.TYPE_MESSAGE
    types = fd.message_type
    message(types, "XStat", [
        ("metadata_id", 1, i64, one, None, False),
        ("double_value", 2, F.TYPE_DOUBLE, one, None, True),
        ("uint64_value", 3, u64, one, None, True),
        ("int64_value", 4, i64, one, None, True),
        ("str_value", 5, s, one, None, True),
        ("bytes_value", 6, F.TYPE_BYTES, one, None, True),
        ("ref_value", 7, u64, one, None, True)], oneof="value")
    message(types, "XEvent", [
        ("metadata_id", 1, i64, one, None, False),
        ("offset_ps", 2, i64, one, None, True),
        ("num_occurrences", 5, i64, one, None, True),
        ("duration_ps", 3, i64, one, None, False),
        ("stats", 4, msg, many, "XStat", False)], oneof="data")
    message(types, "XLine", [
        ("id", 1, i64, one, None, False),
        ("name", 2, s, one, None, False),
        ("timestamp_ns", 3, i64, one, None, False),
        ("events", 4, msg, many, "XEvent", False)])
    message(types, "XEventMetadata", [
        ("id", 1, i64, one, None, False),
        ("name", 2, s, one, None, False),
        ("stats", 5, msg, many, "XStat", False)])
    message(types, "XStatMetadata", [
        ("id", 1, i64, one, None, False),
        ("name", 2, s, one, None, False)])
    plane = message(types, "XPlane", [
        ("id", 1, i64, one, None, False),
        ("name", 2, s, one, None, False),
        ("lines", 3, msg, many, "XLine", False),
        ("event_metadata", 4, msg, many, "XPlane.EventMetadataEntry", False),
        ("stat_metadata", 5, msg, many, "XPlane.StatMetadataEntry", False),
        ("stats", 6, msg, many, "XStat", False)])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = message(plane.nested_type, entry, [
            ("key", 1, i64, one, None, False),
            ("value", 2, msg, one, value, False)])
        e.options.map_entry = True
    message(types, "XSpace", [("planes", 1, msg, many, "XPlane", False)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _stat(plane, stats, key: str) -> Optional[str]:
    for st in stats:
        if plane.stat_metadata[st.metadata_id].name != key:
            continue
        kind = st.WhichOneof("value")
        if kind == "ref_value":         # an interned string
            return plane.stat_metadata[st.ref_value].name
        return str(getattr(st, kind)) if kind else None
    return None


def _is_device(name: str) -> bool:
    # the chips' planes, as devtrace.load picks them
    return name.startswith("/device:") and "TPU" in name \
        and "Core" not in name


def parse(data: bytes) -> Profile:
    """Op events with their stage, program executions and ``repro:``
    host spans of a serialized XSpace (times in ns on the profiler's
    clock, the clock ``devtrace.load`` reads). Of several chips, the
    first, as there."""
    space = xspace_class().FromString(data)
    chips, host = [], []
    for plane in space.planes:
        device = _is_device(plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        meta = plane.event_metadata
        ops, modules = [], []
        where: Dict[int, Optional[str]] = {}
        for line in plane.lines:
            base_ps = line.timestamp_ns * 1000
            for e in line.events:
                name = meta[e.metadata_id].name
                t = ((base_ps + e.offset_ps) // 1000, e.duration_ps // 1000)
                if not device:
                    if name.startswith(HOST_PREFIX):
                        host.append((name,) + t)
                elif line.name == devtrace.OPS_LINE:
                    if e.metadata_id not in where:
                        path = (_stat(plane, meta[e.metadata_id].stats,
                                      SCOPE_STAT)
                                or _stat(plane, e.stats, SCOPE_STAT))
                        where[e.metadata_id] = scope(path)
                    ops.append((name,) + t + (where[e.metadata_id],))
                elif line.name == MODULES_LINE:
                    modules.append((name,) + t)
        if device and ops:
            chips.append((ops, modules))
    ops, modules = chips[0] if chips else ([], [])
    return Profile(ops=ops, modules=modules, host=host)


@functools.lru_cache(maxsize=4)
def _load(path: str, mtime: float) -> Profile:
    with open(path, "rb") as f:
        return parse(f.read())


def load(directory: str) -> Optional[Profile]:
    """The newest ``.xplane.pb`` under ``directory``; None if none, or if
    the protobuf runtime is missing."""
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None
    try:
        return _load(paths[-1], os.path.getmtime(paths[-1]))
    except ImportError:
        return None


def _agreed(found) -> Optional[str]:
    if found == {"select", "walk"}:
        return "select"
    return next(iter(found)) if len(found) == 1 else None


def resolve(ops) -> List[Optional[str]]:
    """Each op's stage: its own scope; for an op with none, that of the
    op event it runs inside (resolved first), else the one stage the
    scoped ops nested in it agree on (the walk inside select counting as
    select); else None."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    parent: List[Optional[int]] = [None] * len(ops)
    stack: List[int] = []
    for i in order:
        s, e = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            parent[i] = stack[-1]
        stack.append(i)
    inside: List[set] = [set() for _ in ops]
    for i in reversed(order):           # children before their parents
        p = parent[i]
        if p is not None:
            inside[p] |= inside[i]
            if ops[i][3]:
                inside[p].add(ops[i][3])
    out: List[Optional[str]] = [None] * len(ops)
    for i in order:                     # parents before their children
        p = parent[i]
        out[i] = (ops[i][3] or (out[p] if p is not None else None)
                  or _agreed(inside[i]))
    return out


def stage_ns(ops, window: Tuple[int, int]) -> Dict[str, int]:
    """Device self time (ns) by stage inside the window; ``select``
    includes ``walk``, and ``unscoped`` holds the ops on no stage. A
    nested op counts once, with the innermost op, and is attributed by
    its stage (``resolve``)."""
    lo, hi = window
    mine = [(n, s, d) for n, s, d, _ in ops]
    out: Dict[str, int] = defaultdict(int)
    for (_, s, own), st in zip(devtrace.self_times(mine), resolve(ops)):
        if lo <= s < hi:
            out[st or "unscoped"] += own
            if st == "walk":
                out["select"] += own
    return {k: out.get(k, 0) for k in STAGES + ("unscoped",)}


def _profile(ctx) -> Optional[Profile]:
    tr = ctx.trace
    if tr is None or not tr.annotations:
        return None
    name = ctx.cell["workload"]["name"]
    return load(os.path.join(ctx.root, "bench_out", name, "profile"))


def stage_ms(ctx, stage: str) -> Optional[float]:
    """A stage's device ms per traced experiment (``stage`` is one of
    ``STAGES`` or ``unscoped``); None where the profile is missing or
    no op carries a stage (a program without the scopes)."""
    prof = _profile(ctx)
    if prof is None or not any(op[3] for op in prof.ops):
        return None
    ns = stage_ns(prof.ops, ctx.trace.window)[stage]
    return ns / 1e6 / len(ctx.trace.annotations)


def idle_between_programs_ms(ctx) -> Optional[float]:
    """Device ms per traced experiment with no program executing."""
    prof = _profile(ctx)
    if prof is None or not prof.modules:
        return None
    gaps = devtrace.gaps(ctx.trace._replace(ops=prof.modules))
    return sum(b - a for a, b in gaps) / 1e6 / len(ctx.trace.annotations)


def report(directory: str, k: int = 8) -> Dict[str, object]:
    """Stage ms, idle between and inside programs, and the ``k`` largest
    idle gaps with where they fall, over a recorded profile's traced
    experiments."""
    prof = load(directory)
    tr = devtrace.load(directory)
    if prof is None or tr is None or not tr.annotations:
        return {}
    n, win = len(tr.annotations), tr.window
    between = devtrace.gaps(tr._replace(ops=prof.modules))
    holes = sorted(devtrace.gaps(tr), key=lambda g: g[0] - g[1])[:k]

    def where(a, b):
        mid = (a + b) // 2
        inside = any(s <= mid < s + d for _, s, d in prof.modules)
        return ["inside a program" if inside else "between programs",
                devtrace.label(mid, tr, prof.host), (b - a) / 1e6]

    return {"experiments": n,
            "busy_ms": devtrace.busy_ns(tr) / 1e6 / n,
            "stage_ms": {k_: v / 1e6 / n for k_, v in
                         stage_ns(prof.ops, win).items()},
            "idle_between_programs_ms":
                sum(b - a for a, b in between) / 1e6 / n,
            "largest_idle_gaps_ms": [where(a, b) for a, b in holes]}


if __name__ == "__main__":
    import json
    print(json.dumps(report(sys.argv[1]), indent=1))
