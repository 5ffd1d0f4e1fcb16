"""Process start to the window's start: weights, compiles or cache reads, warm-up (s)."""


def read(ctx):
    return ctx.setup_s
