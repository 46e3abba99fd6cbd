"""Device self time of the ops under ``hfl.eval`` (the block-end test-set
evaluation), per traced experiment (milliseconds; ``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "eval")
