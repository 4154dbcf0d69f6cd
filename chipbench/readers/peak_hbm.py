"""Peak device memory of the fullest chip, as the runtime reports it."""


def read(ctx):
    if not ctx["memory_peak_bytes"]:
        return None
    return ctx["memory_peak_bytes"] / 1e9
