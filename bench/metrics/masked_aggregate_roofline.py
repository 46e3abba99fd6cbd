"""``masked_aggregate``'s share of its roofline in the traced stretch (percent;
``bench/roofline.py``, cost in ``bench/kernels/masked_aggregate.py``)."""
from bench import roofline


def read(ctx):
    return roofline.share(ctx, "masked_aggregate")
