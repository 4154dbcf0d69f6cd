"""Sums over the window's ``RoundStats`` (``backend.last_round_stats``
of every fit): host clocks around real enqueues and real blocking
waits."""

#: keys under which a dispatch path books host time BLOCKED on the device
WAIT_KEYS = ("flags_wait_s", "gather_wait_s", "rung_wait_s")


def _with_finalize(stats):
    """A fit's stats and, for the compacted loop, its finalize pass's."""
    yield stats
    if isinstance(stats.get("finalize"), dict):
        yield stats["finalize"]


def total(fits, keys):
    return sum(float(part.get(k) or 0.0)
               for f in fits if f["stats"]
               for part in _with_finalize(f["stats"]) for k in keys)


def dispatch_s(fits):
    return total(fits, ("dispatch_s",))


def wait_s(fits):
    return total(fits, WAIT_KEYS)


def rounds(fits):
    return total(fits, ("rounds",))
