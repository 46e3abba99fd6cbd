"""A run with the timed path broken underneath (``bench/faults.py``),
driven past the look for a chip, must come out not correct."""
import json
import os
import subprocess
import sys

import pytest

from bench.run import ROOT

CASES = [("metropolis-1k.train", f) for f in ("frozen", "half_batch",
                                               "altered")] + [
    ("metropolis-1k.bandit", "altered")]
# the cohort cells at their own traffic (seconds on the CPU)
SIZES = {"metropolis-1k.train": {}, "metropolis-1k.bandit": {}}

RUNNER = """
import sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import faults, run
if {fault!r}:
    faults.FAULTS[{fault!r}]()
sys.exit(run.run_cell({workload!r}, 2 ** 31 + 3, 0.1, False,
                      require_tpu=False, overrides={sizes!r}))
"""


def drive(workload, fault):
    code = RUNNER.format(root=ROOT, src=os.path.join(ROOT, "src"),
                         fault=fault, workload=workload,
                         sizes=SIZES[workload])
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_not_correct(workload, fault):
    line = drive(workload, fault)
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("workload", sorted(SIZES))
def test_same_run_without_a_fault_is_correct(workload):
    assert drive(workload, "")["correct"] is True
