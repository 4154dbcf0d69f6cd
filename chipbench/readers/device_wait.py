"""Share of the window the host spent blocked on device results."""

from chipbench.readers import _stats


def read(ctx):
    if not _stats.rounds(ctx["fits"]):
        return None
    return 100.0 * _stats.wait_s(ctx["fits"]) / ctx["elapsed"]
