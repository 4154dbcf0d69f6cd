"""Programs built, traced or compiled inside the window (the compile
cache's miss counters; reading an exported program is no compile)."""


def read(ctx):
    return float(sum(ctx["compiles"].values()))
