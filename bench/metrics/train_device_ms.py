"""Device self time of the ops under ``hfl.train`` (packing, minibatch
gathers, the slot-parameter broadcast, Eq. 2 local SGD), per traced
experiment (milliseconds; ``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "train")
