"""Host milliseconds to slice, place and enqueue one device round."""

from chipbench.readers import _stats


def read(ctx):
    rounds = _stats.rounds(ctx["fits"])
    if not rounds:
        return None
    return 1000.0 * _stats.dispatch_s(ctx["fits"]) / rounds
