"""Device time of the jitted round program (the vectorized runtime's
``round_fn``) per round in the traced window, from the trace's program
executions."""
from bench.trace import module_ns

PROGRAM = "jit_round_fn"


def read(ctx):
    tr = ctx.trace
    if tr is None or not ctx.window.rounds:
        return None
    ns = module_ns(tr, PROGRAM)
    if ns <= 0:
        return None
    return ns * 1e-6 / ctx.window.rounds
