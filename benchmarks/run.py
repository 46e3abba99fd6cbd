"""Benchmark runner: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Quick mode by default;
REPRO_BENCH_FULL=1 restores paper-scale horizons. ``--json PATH``
merges the rows by name into the JSON list at PATH (e.g.
``BENCH_quick.json``), annotating re-measured entries with a
``speedup_vs`` ratio against the previous value, so the perf trajectory
accumulates across PRs (uploaded as a CI artifact; guarded by
``benchmarks/check_regression.py``).

Output goes through ``repro.obs.logging_setup`` — default stdout is
byte-identical to the historical ``print`` CSV; ``-v`` adds timestamped
DEBUG records, ``--quiet`` keeps only warnings. Set
``REPRO_TRACE=bench.jsonl`` (optionally ``REPRO_TRACE_PERFETTO=...``)
to capture a span trace of every run the suite dispatches.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks.common import Row, emit, write_json
from repro.compile_cache import enable_compile_cache
from repro.obs.logging_setup import (add_logging_args, get_logger,
                                     setup_from_args)

MODULES = [
    "benchmarks.fig2_participation",
    "benchmarks.fig3_convex_utility",
    "benchmarks.fig4_training",
    "benchmarks.fig4_budget",
    "benchmarks.fig4_deadline",
    "benchmarks.fig567_nonconvex",
    "benchmarks.ablation_phased",
    "benchmarks.engine_sweep",
    "benchmarks.sweep_training",
    "benchmarks.grid_bench",
    "benchmarks.env_bench",
    "benchmarks.kernels_bench",
    "benchmarks.roofline_report",
    "benchmarks.trials_bench",
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the rows as a JSON list to PATH "
                         "(convention: BENCH_<name>.json)")
    add_logging_args(ap)
    args = ap.parse_args(argv)
    setup_from_args(args)
    enable_compile_cache()
    log = get_logger("repro.bench")
    log.info("name,us_per_call,derived")
    all_rows: list[Row] = []
    failures = 0
    for modname in MODULES:
        log.debug("running %s", modname)
        try:
            mod = __import__(modname, fromlist=["run"])
            rows = mod.run()
            emit(rows)
            all_rows.extend(rows)
        except Exception as e:  # noqa: BLE001 — keep the suite going
            failures += 1
            log.error(f"{modname},,ERROR:{type(e).__name__}:{e}")
            all_rows.append((modname, None, f"ERROR:{type(e).__name__}:{e}"))
            traceback.print_exc(file=sys.stderr)
    if args.json:
        write_json(all_rows, args.json)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
