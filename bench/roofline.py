"""A kernel's share of its roofline in the traced stretch.

The kernel's module (``bench/kernels/<kernel>.py``) names its events
(``NAMES``: substrings of the device op names the trace prints) and
gives the operations and bytes one event needs (``cost(cell, result)``,
from the cell's shapes and the experiment's selections). The least time the chip could take for an event is
``max(ops / peak FLOP/s, bytes / peak bytes/s)``; the share is that
least time over all matched events, over their measured device time.
"""
from __future__ import annotations

from typing import Optional, Sequence

from bench import cells, devtrace


def events(trace, names: Sequence[str]):
    lo, hi = trace.window
    return [e for e in trace.ops
            if any(n in e[0] for n in names) and lo <= e[1] < hi]


def share(ctx, kernel: str) -> Optional[float]:
    """Percent, or None where the trace holds no event of the kernel."""
    if ctx.trace is None or not ctx.results:
        return None
    mod = cells.module(ctx.root, "kernels", kernel)
    ev = events(ctx.trace, mod.NAMES)
    if not ev:
        return None
    ops, nbytes = mod.cost(ctx.cell, ctx.results[0])
    least = len(ev) * max(ops / ctx.peaks["bf16_flops_per_s"],
                          nbytes / ctx.peaks["hbm_bytes_per_s"])
    spent = sum(b - a for a, b in devtrace.clip(
        [(s, s + d) for _, s, d in ev], ctx.trace.window)) / 1e9
    return 100.0 * least / spent
