"""Cost of one ``context_pairwise`` event: the fused Eq. 4/5 stage of one
round for every seed of the experiment (the kernel is vmapped over
seeds, one launch per round). Bytes: positions, bandwidth and compute
per client, ES coordinates, two (N, M) fading draws in; distance, gain,
rate and latency (N, M) out; all float32, at the unpadded N. Operations:
about 40 elementwise operations per pair (distance, path loss, three
Shannon rates, the latency sum)."""

NAMES = ("context_pairwise",)
# the program's tile autotuners for this kernel (bench/tiles.py)
AUTOTUNERS = ("repro.kernels.context_pairwise.ops.best_tile",)
OPS_PER_PAIR = 40


def cost(cell, result):
    w = cell["config"]["world"]
    n, m = w["num_clients"], w["num_edge_servers"]
    s = int(cell["traffic"]["seeds"])
    nbytes = 4 * (2 * n + 2 * m + n + n + 2 * n * m) + 4 * 4 * n * m
    return s * OPS_PER_PAIR * n * m, s * nbytes
