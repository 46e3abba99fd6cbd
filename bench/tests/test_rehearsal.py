"""Each cell's window loop driven on the CPU for a tiny experiment with
the kernels in interpret mode, and the command's refusals."""
import dataclasses as dc
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.run import ROOT

# the CNN at full width takes minutes a round on the CPU
SMALL_CNN = {"data": {"shape": [8, 8, 3], "samples_per_client": 40,
                     "test_samples": 100},
            "model": {"input_shape": [8, 8, 3]}}
TINY = {"paper-cifar10.train": {
            "config": SMALL_CNN,
            "traffic": {"horizon": 2, "seeds": 1, "eval_every": 1,
                        "batch_size": 8, "env_seeds": [2112009250]}},
        "metropolis-1k.train": {
            "traffic": {"horizon": 3, "seeds": 2, "eval_every": 2,
                        "env_seeds": [2112009250, 2112009251]}},
        "metropolis-1k.bandit": {
            "traffic": {"horizon": 3, "seeds": 2,
                        "env_seeds": [2112009250, 2112009251]}}}


def kernels_on(spec):
    """Every Pallas kernel on (interpret mode on the CPU)."""
    return dc.replace(
        spec, env=dc.replace(spec.env, use_kernel=True),
        train=(dc.replace(spec.train, use_kernel=True)
               if spec.train is not None else None),
        policy=dc.replace(spec.policy, options=spec.policy.options
                          + (("use_kernel", True),)))


def last_json(out: str):
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", sorted(TINY))
def test_window_loop_on_cpu(workload, capsys):
    rc = run.run_cell(workload, 2 ** 31 + 5, 0.1, False, require_tpu=False,
                      overrides=TINY[workload], spec_hook=kernels_on)
    assert rc == 0
    line = last_json(capsys.readouterr().out)
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"seed_rounds_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    # on the CPU the program and the reference agree exactly
    assert {k: v["value"] for k, v in line["checks"].items()} == \
        {k: 0.0 for k in line["checks"]}
    assert line["correct"] is True
    assert list(line)[-1] == "checks"


def test_traced_run_reports_span_metrics(capsys):
    rc = run.run_cell("metropolis-1k.train", 11, 0.1, True, require_tpu=False,
                      overrides=TINY["metropolis-1k.train"])
    assert rc == 0
    line = last_json(capsys.readouterr().out)
    m = line["metrics"]
    for name in ("facade_ms", "prescan_ms", "dispatch_ms"):
        assert m[name]["value"] > 0 and m[name]["unit"] == "ms"
    assert m["window_compiles"]["value"] == 0
    # no device plane on the CPU: the trace-based metrics stay silent
    for name in ("device_idle_share", "context_pairwise_roofline"):
        assert name not in m
    shutil.rmtree(os.path.join(ROOT, "bench_out", "metropolis-1k.train"),
                  ignore_errors=True)


def command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "metropolis-1k.train",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


def test_command_refuses_without_a_tpu():
    p = command(ROOT)
    assert p.returncode != 0 and no_result(p.stdout)
    assert "needs 1 TPU" in p.stderr


def test_command_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = command(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0 and no_result(p.stdout)


def test_unknown_device_is_an_error():
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        run.peaks_for("TPU v99 imaginary")
