"""Share of the window the host spent neither enqueueing rounds nor
blocked on the device: CV splitting, placement, result assembly."""

from chipbench.readers import _stats


def read(ctx):
    fits = ctx["fits"]
    if not _stats.rounds(fits):
        return None
    inside = _stats.dispatch_s(fits) + _stats.wait_s(fits)
    return 100.0 * (ctx["elapsed"] - inside) / ctx["elapsed"]
