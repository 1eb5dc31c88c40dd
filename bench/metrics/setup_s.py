"""setup_s: process start to the opening of the measured window (weights,
warm-up, compilation or cache loads)."""


def read(ctx):
    return ctx.setup_s
