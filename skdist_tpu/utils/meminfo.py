"""Best-effort memory budgets for densification guardrails.

The reference never densified: Spark broadcast chunks existed
precisely because X was big. The TPU path densifies for the MXU, so it
needs to know — BEFORE allocating — whether a densified sparse input
can exist at all; an uninformative OOM minutes later is the failure
mode this prevents.
"""

import os

#: explicit operator override (bytes) for the densification budget
BUDGET_ENV = "SKDIST_DENSIFY_BUDGET_BYTES"


def available_host_bytes():
    """Currently-available physical host memory, or None off-POSIX."""
    try:
        return os.sysconf("SC_AVPHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    except (ValueError, OSError, AttributeError):
        return None


def _proc_status_kb(field):
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith(field + ":"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        return None
    return None


def rss_bytes():
    """Current resident set size of this process, or None off-Linux —
    the streaming smoke's bounded-host-memory probe."""
    return _proc_status_kb("VmRSS")


def peak_rss_bytes():
    """Lifetime peak resident set size (VmHWM, falling back to
    ``ru_maxrss`` where the kernel omits it), or None where neither
    exists. Monotone: the streaming smoke asserts on the DELTA across
    the out-of-core fit, not the absolute value (the interpreter + jax
    runtime own the baseline)."""
    v = _proc_status_kb("VmHWM")
    if v is not None:
        return v
    try:
        import resource

        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except (ImportError, ValueError, OSError):
        return None


def densify_budget_bytes():
    """(budget, source_description) for a full densified allocation.

    The binding constraint is available host RAM: the dense ndarray is
    built on host, and host feasibility is a prerequisite for every
    downstream path. Free HBM is deliberately NOT part of the bound —
    a mesh with a 'data' axis row-shards X across devices, so one
    device's free HBM is the wrong ceiling (it would reject multi-chip
    fits that are fine); device-side fitting is the job of the
    backend's AOT memory-analysis round sizing and its OOM backstop.
    Returns (None, "") when nothing can be determined.
    """
    env = os.environ.get(BUDGET_ENV)
    if env:
        try:
            return int(float(env)), f"{BUDGET_ENV} override"
        except ValueError:
            pass
    host = available_host_bytes()
    if host:
        return host, "available host RAM"
    return None, ""
