"""A number the program books once per fit in its round stats
(``backend.last_round_stats[key]``: what the compiler says of the step
program, say), as the mean over the window's fits that hold it, times
``scale``. ``None`` where no fit's stats hold ``key`` (a program
without the counter): the metric is left out."""


def read(ctx, key, scale=1.0):
    found = [f["stats"][key] for f in ctx["fits"]
             if f["stats"] and f["stats"].get(key) is not None]
    if not found:
        return None
    return scale * sum(found) / len(found)
