"""The ``mfu`` FLOP arithmetic against hand counts."""
import jax
import numpy as np
import pytest

from bench import cells, reference
from bench.run import ROOT


def hand_cnn_forward():
    # 2 x multiply-accumulates: conv1 (32x32x64 outputs, 5x5x3 taps),
    # conv2 (16x16x64, 5x5x64), FC 4096->384, 384->192, 192->10
    return 2 * (32 * 32 * 64 * 75 + 16 * 16 * 64 * 1600 + 4096 * 384
                + 384 * 192 + 192 * 10)


def test_forward_flops_per_sample_in_the_configs():
    paper = cells.load(ROOT, "paper-cifar10.train")["config"]
    cohort = cells.load(ROOT, "metropolis-1k.train")["config"]
    assert paper["model"]["forward_flops_per_sample"] == hand_cnn_forward()
    assert hand_cnn_forward() == 65_556_224
    assert cohort["model"]["forward_flops_per_sample"] == 2 * 784 * 10


@pytest.mark.parametrize("workload,count", [("paper-cifar10.train", 1_756_426),
                                            ("metropolis-1k.train", 7_850)])
def test_parameter_counts(workload, count):
    cfg = cells.load(ROOT, workload)["config"]
    params = reference.init_params(cfg["model"], 0, np.float32)
    assert sum(int(p.size) for p in jax.tree.leaves(params)) == count
    assert cfg["model"]["parameters"] == count


def test_model_flops_counts_selected_clients_and_evals():
    cell = cells.load(ROOT, "paper-cifar10.train")
    mfu = cells.module(ROOT, "metrics", "mfu")
    sel = -np.ones((2, 20, 50), np.int32)
    sel[0, :, :3] = 1          # 3 picks per round, seed 0
    sel[1, :, 5] = 0           # 1 pick per round, seed 1
    res = {"selections": sel, "loss": np.zeros((2, 4))}
    picks, steps, batch = 20 * 4, 5 * 2, 32
    fwd = hand_cnn_forward()
    want = picks * steps * batch * 3 * fwd + 2 * 4 * 2000 * fwd
    assert mfu.model_flops(cell, res) == pytest.approx(want)


def test_mfu_reads_the_traced_stretch():
    from bench.devtrace import ANNOTATION, Trace
    cell = cells.load(ROOT, "metropolis-1k.train")
    mfu = cells.module(ROOT, "metrics", "mfu")
    sel = np.zeros((4, 200, 1000), np.int32)
    res = {"selections": sel, "loss": np.zeros((4, 40))}
    tr = Trace(ops=[("x", 0, 1)], chips=1,
               annotations=[(f"{ANNOTATION} 0", 0, 10**9),
                            (f"{ANNOTATION} 1", 10**9, 10**9)])

    class Ctx:
        trace, results, peaks = tr, [res], {"bf16_flops_per_s": 1e12}
    Ctx.cell = cell
    flops = 2 * mfu.model_flops(cell, res)
    assert mfu.read(Ctx) == pytest.approx(100 * flops / 2.0 / 1e12)
