"""Process start to the end of the warm-up fit."""


def read(ctx):
    return ctx["setup_s"]
