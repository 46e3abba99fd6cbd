"""Cost of one ``masked_aggregate`` event: Eq. 3 for one edge server of
one seed in one round (one launch each). Bytes: the selected clients'
float32 deltas of D parameters, the edge model in and out; operations:
a multiply-add per selected client and parameter. Padded slots are not
counted: the figure is what the algorithm needs, averaged over the
experiment's (seed, round, ES) launches."""
import numpy as np

NAMES = ("masked_aggregate",)
# the program's tile autotuners for this kernel (bench/tiles.py)
AUTOTUNERS = ("repro.kernels.masked_aggregate.ops.best_tile",)


def cost(cell, result):
    d = cell["config"]["model"]["parameters"]
    m = cell["config"]["world"]["num_edge_servers"]
    sel = np.asarray(result["selections"])
    launches = sel.shape[0] * sel.shape[1] * m
    picks = float((sel >= 0).sum()) / launches
    return 2.0 * picks * d, 4.0 * d * (picks + 2)
