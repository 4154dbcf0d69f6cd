"""Rounds the program dispatched again or had refused, per unit
completed: the sum over ``keys`` of ``round_counts.total`` (``retries``:
re-dispatches after a fault; ``refused``: rounds and probe compiles the
device refused for memory, where the program books them) over the
window's fits. Reads 0 while round sizing's estimate holds. ``None``
where the program books none of the keys: the metric is left out."""

from chipbench.readers import round_counts


def read(ctx, keys):
    found = [round_counts.total(ctx["fits"], key) for key in keys]
    if all(v is None for v in found) or not ctx["units_done"]:
        return None
    return sum(v or 0 for v in found) / ctx["units_done"]
