"""The control: the plain reference computed in bfloat16, put in the
program's place, must come out not correct; the same reference at the
configuration's float32 must come out correct (it replays itself)."""
import jax.numpy as jnp
import pytest

from bench import cells, check, data, reference
from bench.run import ROOT

from bench.tests.test_rehearsal import SMALL_CNN

SIZES = {"paper-cifar10.train": {
             "config": SMALL_CNN,
             "traffic": {"horizon": 5, "seeds": 1, "eval_every": 5,
                         "batch_size": 8, "env_seeds": [2112009250]}},
         "metropolis-1k.train": {},
         "metropolis-1k.bandit": {}}


def judged(workload, dtype, seed=2 ** 31 + 9):
    cell = cells.merge(cells.load(ROOT, workload), SIZES[workload])
    cfg, traffic = cell["config"], cell["traffic"]
    seeds = cells.spec_dict(cell, seed)["seeds"]
    arrays = data.make(cfg, seed) if traffic["train"] else None
    got = reference.simulate(cfg, traffic, arrays, seeds, dtype)
    ref = reference.replay(cfg, traffic, arrays, seeds, got)
    nums = check.numbers(got, ref, cfg["world"]["budget"])
    return check.judge(nums, check.limits(workload))[0], nums


@pytest.mark.parametrize("workload", sorted(SIZES))
def test_bfloat16_control_is_not_correct(workload):
    ok, nums = judged(workload, jnp.bfloat16)
    assert not ok, nums


@pytest.mark.parametrize("workload", sorted(SIZES))
def test_float32_reference_replays_itself(workload):
    ok, nums = judged(workload, jnp.float32)
    assert ok, nums
    assert all(v == 0 for v in nums.values()), nums
