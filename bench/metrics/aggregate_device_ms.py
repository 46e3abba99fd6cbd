"""Device self time of the ops under ``hfl.aggregate`` (the deadline mask,
Eq. 3 aggregation, the global sync), per traced experiment
(milliseconds; ``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "aggregate")
