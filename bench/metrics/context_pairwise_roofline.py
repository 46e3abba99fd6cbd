"""``context_pairwise``'s share of its roofline in the traced stretch (percent;
``bench/roofline.py``, cost in ``bench/kernels/context_pairwise.py``)."""
from bench import roofline


def read(ctx):
    return roofline.share(ctx, "context_pairwise")
