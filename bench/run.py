"""The on-chip benchmark: whole ``repro.run`` experiments, back to back.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout on a TPU host. A run:

1. set-up (``setup_s``, from the start of this script): imports, the
   persistent compilation cache at the program's fixed path inside the
   checkout, the kernel-tile picks of the checkout's first run
   (``bench/tiles.py``), the cell's federated dataset made from
   ``--seed`` and staged onto the device once through the program's
   ``FederatedDataset``, and one warm-up experiment of the identical
   spec (it compiles, or loads from the cache, every block shape the
   window uses, and runs the kernel-tile autotuners);
2. the window: one complete ``repro.run(spec, data=dataset)`` after
   another, each ending with its results on the host, started while
   less than ``--seconds`` has elapsed; no result is reused;
3. the check: every result of the window is compared with the first;
   each distinct one is replayed by the plain reference
   (``bench/reference.py``) and held to the limits of
   ``bench/limits/<workload>.json`` (``bench/check.py``);
4. the last line of standard output: one JSON object with ``correct``,
   ``attempted``, ``failed``, ``metrics``, ``device`` and last
   ``checks`` (each compared number beside its limit). With
   ``--trace 0`` the metrics are the cell's end-to-end metrics; with
   ``--trace 1`` the host spans of ``repro.obs`` are on for the whole
   window, the first few experiments run under ``jax.profiler``, and the
   metrics are the cell's per-layer metrics, with ``device.busy_s``,
   ``device.window_s`` and a ``breakdown``.

Without a TPU (or with fewer chips than the cell asks for) it prints no
result and exits non-zero.

Everything a cell needs is found by name, so each of these is new files
plus entries in ``BENCHMARK.json``, with no edit to a file that exists:

* a configuration: ``bench/configs/<name>.json`` (the ``ExperimentSpec``
  fields under ``spec``, the deployment's constants under ``world``, the
  model under ``model``, the data shapes under ``data``, and ``source``,
  ``reduced``, ``assumed``) and an entry of ``configs``;
* a traffic mix: ``bench/traffic/<name>.json`` (``seeds``, ``horizon``,
  ``train``, optionally ``env_seeds`` (the experiment's seeds: a fixed
  set, taken in an order drawn from ``--seed``) and, for training,
  ``eval_every``, ``batch_size``, ``batches_per_epoch``);
* a cell: an entry of ``workloads`` naming the two, and
  ``bench/limits/<workload>.json`` with its correctness limits;
* a per-layer metric: ``bench/metrics/<name>.py`` defining
  ``read(ctx) -> float | None`` (see ``Context`` below) and an entry of
  ``per_layer``;
* a kernel cost function: ``bench/kernels/<kernel>.py`` defining
  ``NAMES`` (the trace's event names of the kernel),
  ``cost(cell, result) -> (ops, bytes)`` per event and ``AUTOTUNERS``
  (the program's tile autotuners of the kernel, dotted paths); a
  ``<kernel>_roofline`` metric module reads it with ``bench/roofline.py``.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cells, check, tiles  # noqa: E402

TRACED_EXPERIMENTS = 3          # experiments run under the profiler, at most
TRACED_SECONDS = 2.0            # ... or until this much has been traced
KEEP_DISTINCT = 3               # distinct results replayed at most
FIELDS = ("selections", "utilities", "participants", "explored",
          "accuracy", "loss")


@dataclass
class Context:
    """What a per-layer metric reads (``bench/metrics/<name>.py``)."""
    cell: Dict[str, Any]            # cells.load(...) of this workload
    spans: List[dict]               # repro.obs records of the window
    trace: Any                      # devtrace.Trace of the traced stretch
    results: List[Dict[str, Any]]   # the distinct window results
    compiles: int                   # backend compiles in the window
    peaks: Dict[str, float]         # this device's row of peaks.json
    root: str = ROOT


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def peaks_for(kind: str, root: str = ROOT) -> Dict[str, float]:
    """The device's peaks; a device missing from the table is an error."""
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def as_arrays(res) -> Dict[str, Any]:
    import numpy as np
    return {k: np.asarray(getattr(res, k)) for k in FIELDS
            if getattr(res, k) is not None}


def same(a: Dict[str, Any], b: Dict[str, Any]) -> bool:
    import numpy as np
    return a.keys() == b.keys() and all(np.array_equal(a[k], b[k],
                                                       equal_nan=True)
                                        for k in a)


class CompileCounter:
    """Backend compiles reported through ``jax.monitoring`` while ``on``
    (``count``), and the persistent cache's hits and misses since the
    last ``reset`` (a miss is a program compiled anew)."""

    def __init__(self):
        import jax
        self.on, self.count, self.hits, self.misses = False, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def reset(self) -> None:
        self.count = self.hits = self.misses = 0

    def _duration(self, name: str, secs: float, **kw) -> None:
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = ROOT, require_tpu: bool = True,
             overrides: Optional[dict] = None,
             spec_hook: Optional[Callable] = None) -> int:
    """One benchmark run (the command's body). ``overrides`` (merged into
    the cell's ``config``/``traffic``) and ``spec_hook`` exist for the
    CPU tests only, which run the cells at tiny sizes."""
    cell = cells.merge(cells.load(root, workload), overrides or {})
    chips = int(cell["workload"]["chips"])
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and (dev.platform != "tpu" or len(devs) < chips):
        log(f"bench: needs {chips} TPU chip(s); JAX sees {len(devs)} "
            f"{dev.platform} device(s)")
        return 2
    peaks = peaks_for(dev.device_kind, root) if require_tpu else {}

    import repro
    from repro import api
    from bench import data as bench_data

    tiles.keep(root, dev.device_kind, tiles.autotuners(root))

    cfg, traffic = cell["config"], cell["traffic"]
    spec = api.ExperimentSpec.from_dict(cells.spec_dict(cell, seed))
    if spec_hook is not None:
        spec = spec_hook(spec)
    seeds = [int(s) for s in spec.seeds]
    arrays, dataset = None, None
    if traffic.get("train"):
        arrays = bench_data.make(cfg, seed)
        dataset = bench_data.federated(arrays)
        jax.block_until_ready(dataset.stacked().x)
    log(f"bench: data ready at {time.perf_counter() - T0:.3f}s")
    warm = repro.run(spec, data=dataset)
    first = as_arrays(warm)
    del warm
    setup_s = time.perf_counter() - T0
    log(f"bench: {workload} seed={seed} set-up {setup_s:.3f}s, "
        f"compile cache {cache_dir}: {counter.hits} hits, "
        f"{counter.misses} misses")

    out_dir = os.path.join(root, "bench_out", workload)
    spans_path = os.path.join(out_dir, "spans.jsonl")
    prof_dir = os.path.join(out_dir, "profile")
    from repro.obs import trace as obs_trace
    tracer = None
    if trace:
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(out_dir, exist_ok=True)
        tracer = obs_trace.trace_to(spans_path)
        tracer.__enter__()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no Python call events
        opts.host_tracer_level = 1        # the harness's annotations
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
    profiling = bool(trace)
    anchors_host: List[int] = []
    rng = random.Random(seed)
    kept: List[Dict[str, Any]] = []
    n = differ = failed = 0
    counter.reset()
    counter.on = True
    t0 = time.perf_counter()
    try:
        while time.perf_counter() - t0 < seconds:
            if profiling:
                anchors_host.append(time.perf_counter_ns())
                with jax.profiler.TraceAnnotation(f"bench:experiment {n}"):
                    res = repro.run(spec, data=dataset)
            else:
                res = repro.run(spec, data=dataset)
            got = as_arrays(res)
            del res
            n += 1
            if n <= 3:
                log(f"bench: experiment {n} ends at "
                    f"{time.perf_counter() - t0:.3f}s of the window")
            if profiling and (n == TRACED_EXPERIMENTS
                              or time.perf_counter() - t0 >= TRACED_SECONDS):
                jax.profiler.stop_trace()
                profiling = False
            if n == 1:
                kept.append(got)
            elif not same(got, kept[0]):
                differ += 1
                if len(kept) <= KEEP_DISTINCT:
                    kept.append(got)
                else:       # reservoir sample of the distinct results
                    j = rng.randrange(differ)
                    if j < KEEP_DISTINCT:
                        kept[1 + j] = got
    except Exception as e:      # a failed experiment is counted, not hidden
        failed += 1
        log(f"bench: experiment {n} raised {type(e).__name__}: {e}")
    window_s = time.perf_counter() - t0
    counter.on = False
    if profiling:
        jax.profiler.stop_trace()
    if tracer is not None:
        tracer.__exit__(None, None, None)
    if n and not same(first, kept[0]):
        log("bench: the warm-up's result differs from the window's first")

    mem = [d.memory_stats() or {} for d in devs[:chips]]
    peak_bytes = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    del dataset
    gc.collect()

    readings, t_ref = [], time.perf_counter()
    from bench import reference
    for got in kept:
        ref = reference.replay(cfg, traffic, arrays, seeds, got)
        readings.append(check.numbers(got, ref, cfg["world"]["budget"]))
    nums = check.worst(readings)
    lims = check.limits(workload, os.path.join(root, "bench"))
    correct, rows = check.judge(nums, lims)
    if not lims:        # a cell whose limits are not set yet: show all
        rows = [[k, v, None] for k, v in sorted(nums.items())]
    log(f"bench: {n} experiments in {window_s:.3f}s, {differ} differ from "
        f"the first, {counter.count} compiles in the window, {len(kept)} "
        f"replayed in {time.perf_counter() - t_ref:.3f}s")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips, "memory_peak_bytes": peak_bytes}
    horizon, n_seeds = int(traffic["horizon"]), len(seeds)
    if not trace:
        metrics = {"seed_rounds_per_s": {
            "value": horizon * n_seeds * n / window_s,
            "unit": "seed-rounds/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        metrics = {m["name"]: metrics[m["name"]] for m in cell["end_to_end"]}
        extra = {}
    else:
        from bench import devtrace
        tr = devtrace.load(prof_dir)
        with open(spans_path) as f:
            spans = [json.loads(line) for line in f][1:]
        ctx = Context(cell=cell, spans=spans, trace=tr, results=kept,
                      compiles=counter.count, peaks=peaks)
        metrics = {}
        for m in cell["per_layer"]:
            v = cells.module(root, "metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        extra = {}
        if tr is not None and tr.ops:
            lo, hi = tr.window
            device["busy_s"] = devtrace.busy_ns(tr) / 1e9
            device["window_s"] = (hi - lo) / 1e9
            anchors = [(a[1], h) for a, h in zip(
                sorted(tr.annotations, key=lambda a: a[1]), anchors_host)]
            host = devtrace.spans_on_trace_clock(spans, anchors)
            extra["breakdown"] = devtrace.breakdown(tr, host)
    for name, num, lim in rows:
        log(f"check {name}: {num} (limit {lim})")
    line = {"correct": bool(correct and n > 0 and not failed),
            "attempted": n + failed,
            "failed": failed, "metrics": metrics, "device": device, **extra,
            "checks": {name: {"value": num, "limit": lim}
                       for name, num, lim in rows}}
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        return run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    except (KeyError, FileNotFoundError) as e:
        log(f"bench: {e}")
        return 2


if __name__ == "__main__":
    sys.exit(main())
