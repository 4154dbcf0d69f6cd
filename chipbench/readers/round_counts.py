"""Counts the program's round loop books per fit (``iters``, ``fevals``,
``lane_slots``, ``live_lane_slots`` of ``backend.last_round_stats``),
summed over the window's fits and, like ``_stats.py``, each fit's
finalize part: ``key`` per unit completed, or 100 x ``num`` / ``den``.
``None`` where the program books no such count (its stats lack the key
or hold ``None``): the metric is left out."""

from chipbench.readers import _stats


def total(fits, key):
    """The sum of ``key`` (a number, or a list with one number per
    task) over the fits, or ``None`` if no fit holds it."""
    found = [part[key] for f in fits if f["stats"]
             for part in _stats._with_finalize(f["stats"])
             if part.get(key) is not None]
    if not found:
        return None
    return sum(sum(v) if isinstance(v, (list, tuple)) else v for v in found)


def read(ctx, key=None, num=None, den=None):
    fits = ctx["fits"]
    if key is not None:
        count, base, scale = total(fits, key), ctx["units_done"], 1.0
    else:
        count, base, scale = total(fits, num), total(fits, den), 100.0
    if count is None or not base:
        return None
    return scale * count / base
