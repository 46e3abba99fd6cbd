"""Device self time of the ops under ``hfl.select`` (the CC-MAB index, the
density sort and the budget walk inside it), per traced experiment
(milliseconds; ``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "select")
