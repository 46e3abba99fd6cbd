"""Model FLOPs of the traced experiments over the traced stretch and the
device's bf16 peak (percent). Counted: 3 x the forward FLOPs per sample
for every local SGD step of every selected client (padded slots do not
count) and one forward pass per test sample per evaluation. The models
compute in float32, so the bf16 peak makes this a ceiling."""
import numpy as np


def model_flops(cell, result) -> float:
    """FLOPs one experiment's algorithm requires."""
    cfg, traffic = cell["config"], cell["traffic"]
    fwd = float(cfg["model"]["forward_flops_per_sample"])
    steps = cfg["world"]["local_epochs"] * int(traffic["batches_per_epoch"])
    batch = int(traffic["batch_size"])
    picks = float((np.asarray(result["selections"]) >= 0).sum())
    evals = float(np.asarray(result["loss"]).size)
    return (picks * steps * batch * 3.0 * fwd
            + evals * cfg["data"]["test_samples"] * fwd)


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.ops or not tr.annotations or not ctx.results \
            or "loss" not in ctx.results[0]:
        return None
    lo, hi = tr.window
    flops = model_flops(ctx.cell, ctx.results[0]) * len(tr.annotations)
    return 100.0 * flops / ((hi - lo) / 1e9) / ctx.peaks["bf16_flops_per_s"]
