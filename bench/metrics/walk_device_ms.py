"""Device self time of the ops under ``hfl.walk`` (the budget walk's loop
alone), per traced experiment (milliseconds; ``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "walk")
