"""How unevenly the traced span's device time falls on the chips:
100 x (max - min) / max of the busy seconds per device
(``trace_reduce.reduce``'s ``busy_s_per_device``). Chips that share the
rows of one operand do the same work, so this reads near 0; a refit, a
reduction or a scoring pass that lands on ONE chip shows here. ``None``
— the metric is left out — without a trace, on a trace of one device,
or where no device was busy."""


def read(ctx):
    trace = ctx["trace"]
    busy = list((trace or {}).get("busy_s_per_device", {}).values())
    if len(busy) < 2 or not max(busy):
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
