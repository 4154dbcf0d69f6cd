"""Units completed in the window over the window's elapsed time."""

from chipbench import window


def read(ctx):
    return window.rate(ctx["fits"], ctx["elapsed"])
