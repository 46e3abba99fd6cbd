"""``budgeted_topk``'s share of its roofline in the traced stretch (percent;
``bench/roofline.py``, cost in ``bench/kernels/budgeted_topk.py``)."""
from bench import roofline


def read(ctx):
    return roofline.share(ctx, "budgeted_topk")
