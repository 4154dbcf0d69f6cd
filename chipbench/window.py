"""The measured window: a closed loop of one caller."""

import time


def run_window(one_fit, seconds, clock=time.perf_counter):
    """Start a fit whenever ``elapsed < seconds``; the window ends when
    the fit in progress completes. Returns ``(fits, elapsed)`` where
    each fit is ``one_fit()``'s result with its start and end offsets
    added under ``t0`` / ``t1``. At least one fit runs."""
    fits = []
    start = clock()
    while True:
        t0 = clock() - start
        if fits and t0 >= seconds:
            break
        fit = dict(one_fit())
        fit["t0"], fit["t1"] = t0, clock() - start
        fits.append(fit)
    return fits, fits[-1]["t1"]


def units_done(fits):
    return sum(f["units"] - f["failed"] for f in fits)


def rate(fits, elapsed):
    """All units completed over all the time of the window."""
    return units_done(fits) / elapsed
