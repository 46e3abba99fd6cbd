"""Readings that the correctness limits are set from (``PERF.md``).

    python bench/readings.py --workload <name> --seeds 1,2,3 --what <kind>

``<kind>`` is ``sound`` (the program as it is), ``control`` (the plain
reference computed in bfloat16, in the program's place) or a fault of
``bench/faults.py`` planted in the program. For each seed it prints one
JSON line with the numbers ``bench/check.py`` compares; the timed path
is driven as in a run (``repro.run`` on the cell's spec and dataset).
Run one kind per process: a fault patches the program before its first
compile. Needs a TPU unless ``--cpu`` is given.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", required=True)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--overrides", default="{}",
                    help="JSON merged into the cell (CPU tests only)")
    a = ap.parse_args(argv)
    from bench import cells, check, data as bench_data, faults, reference
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if not a.cpu and jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 2
    if a.what in faults.FAULTS:
        faults.FAULTS[a.what]()
    elif a.what not in ("sound", "control"):
        raise SystemExit(f"unknown kind {a.what!r}")
    import repro
    from repro import api
    cell = cells.merge(cells.load(ROOT, a.workload),
                       json.loads(a.overrides))
    cfg, traffic = cell["config"], cell["traffic"]
    for seed in [int(s) for s in a.seeds.split(",")]:
        t0 = time.perf_counter()
        d = cells.spec_dict(cell, seed)
        arrays = bench_data.make(cfg, seed) if traffic.get("train") else None
        if a.what == "control":
            got = reference.simulate(cfg, traffic, arrays, d["seeds"],
                                     jnp.bfloat16)
        else:
            dataset = (bench_data.federated(arrays) if arrays is not None
                       else None)
            res = repro.run(api.ExperimentSpec.from_dict(d), data=dataset)
            got = {k: getattr(res, k) for k in
                   ("selections", "utilities", "participants", "explored",
                    "accuracy", "loss") if getattr(res, k) is not None}
            del res, dataset
        ref = reference.replay(cfg, traffic, arrays, d["seeds"], got)
        print(json.dumps({"workload": a.workload, "what": a.what,
                          "seed": seed,
                          "numbers": check.numbers(got, ref,
                                                   cfg["world"]["budget"]),
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
