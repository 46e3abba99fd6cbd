"""Each kernel's operations and bytes per trace event, at the cells'
shapes, against hand counts; and the roofline share built from them."""
import numpy as np
import pytest

from bench import cells, roofline
from bench.devtrace import ANNOTATION, Trace
from bench.run import ROOT

CELLS = ("paper-cifar10.train", "metropolis-1k.train", "metropolis-1k.bandit")
TRAIN_CELLS = CELLS[:2]


def shapes(workload):
    cell = cells.load(ROOT, workload)
    w = cell["config"]["world"]
    return cell, w["num_clients"], w["num_edge_servers"], int(cell["traffic"]["seeds"])


@pytest.mark.parametrize("workload", CELLS)
def test_context_pairwise_cost(workload):
    cell, n, m, s = shapes(workload)
    ops, nbytes = cells.module(ROOT, "kernels", "context_pairwise").cost(cell, None)
    # in: positions (N,2), ES (M,2), bandwidth, compute, two (N,M) fadings;
    # out: distance, gain, rate, latency (N,M); float32, every seed
    assert nbytes == s * 4 * (2 * n + 2 * m + 2 * n + 2 * n * m + 4 * n * m)
    assert ops == s * 40 * n * m


@pytest.mark.parametrize("workload", CELLS)
def test_budgeted_topk_cost(workload):
    cell, n, m, s = shapes(workload)
    ops, nbytes = cells.module(ROOT, "kernels", "budgeted_topk").cost(cell, None)
    assert (ops, nbytes) == (0.0, s * n * m * (4 + 4 + 4))


@pytest.mark.parametrize("workload", TRAIN_CELLS)
def test_masked_aggregate_cost(workload):
    cell, n, m, s = shapes(workload)
    t = int(cell["traffic"]["horizon"])
    d = cell["config"]["model"]["parameters"]
    sel = -np.ones((s, t, n), np.int32)
    sel[:, :, :m * 3] = np.arange(m * 3) % m      # 3 picks per ES a round
    ops, nbytes = cells.module(ROOT, "kernels", "masked_aggregate").cost(
        cell, {"selections": sel})
    assert ops == pytest.approx(2 * 3 * d)
    assert nbytes == pytest.approx(4 * d * (3 + 2))


def test_roofline_share_from_cost_and_event_time():
    cell, n, m, s = shapes("metropolis-1k.train")
    _, nbytes = cells.module(ROOT, "kernels", "context_pairwise").cost(cell, None)
    least_ns = nbytes / 819e9 * 1e9
    tr = Trace(ops=[("%context_pairwise_kernel.8 = ...", 0, int(4 * least_ns)),
                    ("%fusion.1", 0, 10)],
               annotations=[(f"{ANNOTATION} 0", 0, 10 ** 9)], chips=1)

    class Ctx:
        trace, results, root = tr, [{}], ROOT
        peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    Ctx.cell = cell
    assert roofline.share(Ctx, "context_pairwise") == pytest.approx(25.0, rel=1e-3)
    assert roofline.share(Ctx, "masked_aggregate") is None
