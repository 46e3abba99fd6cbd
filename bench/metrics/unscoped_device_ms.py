"""Device self time of the ops under no ``hfl.*`` stage scope, per traced
experiment (milliseconds; ``bench/stages.py``): the scan's own loop, the
outputs' stacking, and whatever a new code path leaves unscoped."""
from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "unscoped")
