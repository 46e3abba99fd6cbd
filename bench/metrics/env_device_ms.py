"""Device self time of the ops under ``hfl.env`` (Eq. 4-6: draws,
``context_pairwise``, outcomes), per traced experiment (milliseconds;
``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "env")
