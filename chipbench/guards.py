"""What makes a fit count as failed: the work left its path. Copies of
``chip_smoke.py``'s guards (PR 22), reading the program's own counters
and warnings."""

import contextlib
import warnings

#: fault counters that mean a round, a lane or an exception was
#: absorbed on the way (``skdist_tpu.parallel.faults.FAULT_COUNTERS``)
WATCHED_FAULTS = (
    "rounds_retried", "retries_exhausted", "lanes_quarantined",
    "suppressed", "watchdog_trips", "elastic_shrinks",
)
#: warnings that mean the work left its path; raised as errors
FATAL_WARNINGS = (
    "falling back",                 # compacted -> classic, export tier
    "exhausted device memory",      # reactive OOM shrink of a round
)
#: compile-shaped counters of ``compile_cache.snapshot()``; reading the
#: export tier's files (``aot_export_hits``) is no compile
COMPILE_KEYS = ("kernel_misses", "jit_misses", "aot_misses",
                "aot_export_writes")


@contextlib.contextmanager
def strict_warnings():
    """Fit-failed and host-fallback warnings become errors; the rest
    (the round sizing's notice) are collected."""
    from skdist_tpu.distribute.search import FitFailedWarning

    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        warnings.filterwarnings("error", category=FitFailedWarning)
        for pat in FATAL_WARNINGS:
            warnings.filterwarnings("error", message=f".*{pat}")
        yield seen


def rounds_fault(stats, n_tasks):
    """Why this dispatch does not count, or None: it did not go through
    the backend's round loop for every task, or a round was retried."""
    if stats is None or stats.get("tasks") != n_tasks:
        return (f"no device dispatch of {n_tasks} tasks on the backend "
                f"(last_round_stats={stats}): the host path ran")
    if stats.get("retries"):
        return f"rounds retried: {stats.get('retries')}"
    return None


def moved_faults(before, after):
    return {k: after[k] - before.get(k, 0) for k in WATCHED_FAULTS
            if after.get(k, 0) - before.get(k, 0)}


def compile_delta(before, after):
    return {k: after[k] - before[k] for k in COMPILE_KEYS}


def guarded(fit, n_units):
    """Run ``fit() -> (units_failed, round_stats, answer)`` under the
    guards. Returns the window's record of it; a fit that raised, or
    during which a fault counter moved, fails all its units."""
    from skdist_tpu.parallel import faults

    before = faults.snapshot()
    record = {"units": n_units, "failed": n_units, "stats": None,
              "answer": None, "why": None}
    try:
        with strict_warnings():
            failed, stats, answer = fit()
    except Exception as exc:  # the boundary: a failed fit is counted
        import traceback

        traceback.print_exc()
        record["why"] = f"{type(exc).__name__}: {exc}"[:500]
        return record
    record.update(stats=stats, answer=answer)
    why = rounds_fault(stats, n_units)
    moved = moved_faults(before, faults.snapshot())
    if moved:
        why = f"fault counters moved: {moved}"
    if why:
        record["why"] = why
        return record
    record["failed"] = int(failed)
    return record
