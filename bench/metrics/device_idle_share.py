"""Share of the traced window in which no operation ran on the device:
1 - (union of the device operations' intervals) / window."""
from bench.trace import busy_ns


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.ops or tr.window_ns <= 0:
        return None
    return 100.0 * (1.0 - busy_ns(tr) / tr.window_ns)
