"""A configuration, traffic mix, cell and per-layer metric added as new
files plus ``BENCHMARK.json`` entries are found by name, with no edit
to any file the benchmark already has."""
import filecmp
import json
import os
import shutil

from bench import cells
from bench.run import ROOT


def test_new_files_are_picked_up_by_name(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    b = os.path.join(root, "bench")

    with open(os.path.join(b, "configs", "metropolis-1k.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "probe-config"
    cfg["world"]["budget"] = 6.0
    with open(os.path.join(b, "configs", "probe-config.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "probe-mix.json"), "w") as f:
        json.dump({"train": False, "seeds": 3, "horizon": 7}, f)
    with open(os.path.join(b, "metrics", "probe_metric.py"), "w") as f:
        f.write("def read(ctx):\n    return 42.0 + len(ctx.spans)\n")
    with open(os.path.join(b, "limits", "probe-config.probe-mix.json"), "w") as f:
        json.dump({"limits": {"selection_rows_differ": 0.5}}, f)

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "probe-config", "source": "x",
                             "file": "bench/configs/probe-config.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "probe-config.probe-mix",
                               "config": "probe-config",
                               "traffic": "probe-mix", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "probe_metric", "unit": "count",
                               "better": "lower", "source": "program_span",
                               "layer": "facade", "moves": "seed_rounds_per_s",
                               "workloads": ["probe-config.probe-mix"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = cells.load(root, "probe-config.probe-mix")
    assert cell["config"]["world"]["budget"] == 6.0
    assert cell["traffic"]["horizon"] == 7
    assert "probe_metric" in [m["name"] for m in cell["per_layer"]]
    assert cells.spec_dict(cell, 5)["seeds"] == [5, 6, 7]
    # a traffic mix that lists its seeds keeps them whatever --seed is,
    # in an order drawn from --seed
    fixed = cells.merge(cell, {"traffic": {"env_seeds": [40, 41, 42]}})
    orders = {tuple(cells.spec_dict(fixed, s)["seeds"])
              for s in (5, 6, 7, 2 ** 33)}
    assert {tuple(sorted(o)) for o in orders} == {(40, 41, 42)}
    assert len(orders) > 1
    assert cells.spec_dict(fixed, 5) == cells.spec_dict(fixed, 5)

    class Ctx:
        spans = [{}, {}]
    assert cells.module(root, "metrics", "probe_metric").read(Ctx) == 44.0
    from bench import check
    assert check.limits("probe-config.probe-mix", b) == {
        "selection_rows_differ": 0.5}
    # the new metric is not reported in the cells that do not list it
    old = cells.load(root, "metropolis-1k.train")
    assert "probe_metric" not in [m["name"] for m in old["per_layer"]]

    # every file the benchmark had is byte for byte as it was
    for d, _, files in os.walk(os.path.join(ROOT, "bench")):
        if "__pycache__" in d or os.sep + "tests" in d:
            continue
        for name in files:
            src = os.path.join(d, name)
            dst = os.path.join(b, os.path.relpath(src, os.path.join(ROOT, "bench")))
            assert filecmp.cmp(src, dst, shallow=False), dst
