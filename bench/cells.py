"""Finding a cell's files by the names in ``BENCHMARK.json``.

A workload names a configuration (``configs[].file``) and a traffic mix
(``bench/traffic/<traffic>.json``); its per-layer metrics are the
entries of ``per_layer`` whose ``workloads`` list it (or that have no
such list), each read by ``bench/metrics/<name>.py``; each kernel whose
roofline a metric reports has its cost function in
``bench/kernels/<kernel>.py``; its correctness limits are in
``bench/limits/<workload>.json``. Nothing here names a cell.
"""
from __future__ import annotations

import importlib.util
import json
import os
import random
from typing import Any, Dict

MAX_SEED = 2 ** 32 - 1024     # spec seeds stay inside uint32 (seed + 11 too)


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def load(root: str, workload: str) -> Dict[str, Any]:
    """The cell's workload entry, configuration, traffic and metric
    entries. Raises ``KeyError`` for a workload ``BENCHMARK.json`` does
    not define."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; defined: "
                       f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {"workload": w,
            "config": _json(os.path.join(root, conf["file"])),
            "traffic": _json(os.path.join(root, "bench", "traffic",
                                          w["traffic"] + ".json")),
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)],
            "run_seconds": bench["run_seconds"]}


def merge(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    """``base`` with ``over`` merged in, dictionaries key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = (merge(base[k], v) if isinstance(v, dict)
                  and isinstance(base.get(k), dict) else v)
    return out


def spec_dict(cell: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """``ExperimentSpec.from_dict`` input: the configuration's spec
    fields with the traffic's horizon, seeds and training knobs.

    The experiment's seeds (environment, policy, model init and minibatch
    draws) are the traffic's ``env_seeds`` where it lists them, in an
    order drawn from ``--seed``: the seeds set how many clients each
    round selects, so every run of the cell does the same work and
    compiles the same block shapes, and the dataset and the order come
    from ``--seed``. Without the list they are ``(s, s + 1, ...)`` with
    ``s`` the run's ``--seed`` folded into ``[0, MAX_SEED)``."""
    cfg, traffic = cell["config"], cell["traffic"]
    base = int(seed) % MAX_SEED
    d = {k: v for k, v in cfg["spec"].items() if k != "train"}
    d["horizon"] = int(traffic["horizon"])
    if "env_seeds" in traffic:
        d["seeds"] = [int(s) for s in traffic["env_seeds"]]
        if len(d["seeds"]) != int(traffic["seeds"]):
            raise ValueError("traffic: env_seeds must list `seeds` seeds")
        random.Random(int(seed)).shuffle(d["seeds"])
    else:
        d["seeds"] = [base + i for i in range(int(traffic["seeds"]))]
    if traffic.get("train"):
        d["train"] = dict(cfg["spec"].get("train", {}),
                          batch_size=int(traffic["batch_size"]),
                          batches_per_epoch=int(traffic["batches_per_epoch"]))
        d["eval"] = {"eval_every": int(traffic["eval_every"])}
    return d


def module(root: str, kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module."""
    path = os.path.join(root, "bench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
