"""Real local steps over the steps the round program computes: each round
pads every cohort to its longest, C x E slots, and the counting bank counts
the real ones."""


def read(ctx):
    w = ctx.window
    if not w.slots:
        return None
    return 100.0 * w.real_steps / w.slots
