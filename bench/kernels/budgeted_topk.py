"""Cost of one ``budgeted_topk`` density-sort event: every seed's (N, M)
pair densities sorted within their client tiles (one launch per round).
Bytes: the N*M float32 densities in, N*M float32 keys and int32 indices
out, at the unpadded N*M. Operations: compare-exchanges are not held
against the bf16 matmul peak, so the roofline is the bytes'."""

NAMES = ("density_sort",)
# the program's tile autotuners for this kernel (bench/tiles.py)
AUTOTUNERS = ("repro.kernels.budgeted_topk.ops.best_tile",)


def cost(cell, result):
    w = cell["config"]["world"]
    pairs = w["num_clients"] * w["num_edge_servers"]
    s = int(cell["traffic"]["seeds"])
    return 0.0, s * 12 * pairs
