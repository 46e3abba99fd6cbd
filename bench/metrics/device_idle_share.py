"""Share of the traced stretch in which no op ran on the device: one
minus the union of the device op intervals over the window from the
first traced experiment's start to the last's end (percent)."""
from bench import devtrace


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.ops:
        return None
    lo, hi = tr.window
    return 100.0 * (1.0 - devtrace.busy_ns(tr) / (hi - lo))
