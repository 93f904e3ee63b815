"""Programs compiled, or read back from the persistent cache, inside the
window (``jax.monitoring`` events).  Every shape is warmed in set-up, so the
expected count is 0."""


def read(ctx):
    return ctx.window.programs
