"""Round sizing's estimate of what a running round holds against what
the chip held: 100 x (``shared_bytes`` + ``chunk`` x ``lane_bytes``) of
the window's last fit (``backend.last_round_stats``) over
``memory_peak_bytes``. ``None`` where the program books no
``lane_bytes`` (its stats lack the key or hold ``None``): the metric is
left out."""


def read(ctx):
    booked = [f["stats"] for f in ctx["fits"]
              if f["stats"] and f["stats"].get("lane_bytes")
              and f["stats"].get("chunk")]
    if not booked or not ctx["memory_peak_bytes"]:
        return None
    stats = booked[-1]
    return (100.0 * ((stats.get("shared_bytes") or 0)
                     + stats["chunk"] * stats["lane_bytes"])
            / ctx["memory_peak_bytes"])
