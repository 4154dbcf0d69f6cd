"""Seconds per fit inside the program's own span tree.

Reads the program's ring (``skdist_tpu.obs.trace.events()``) in the
same process, after the window: the last ``len(ctx["fits"])``
``search_fit`` roots are the window's (the warm-up fit's is older), and
a root's tree is every span that carries its ``trace_id``. ``span`` is
the root itself or a name summed over the whole tree; ``minus`` takes
off spans of those names that are DIRECT children of the root (a
``place_shared`` that a refit opened under ``refit`` is inside
``refit`` and is not taken off twice). ``None`` — the metric is left
out — when tracing was off, when the ring dropped events, or when it
holds fewer roots than the window has fits (a program without these
spans)."""

ROOT = "search_fit"


def per_fit(events, n_fits, span, minus=()):
    """``events``: the ring's ``(name, ph, t0, dur, tid, args)``
    tuples, oldest first."""
    spans = [e for e in events
             if e[1] == "X" and e[5] and e[5].get("trace_id")]
    roots = [e for e in spans if e[0] == ROOT][-n_fits:]
    if n_fits < 1 or len(roots) < n_fits:
        return None
    seconds = 0.0
    for root in roots:
        ids = root[5]
        tree = [e for e in spans if e is not root
                and e[5]["trace_id"] == ids["trace_id"]]
        seconds += root[3] if span == ROOT else sum(
            e[3] for e in tree if e[0] == span)
        seconds -= sum(e[3] for e in tree if e[0] in minus
                       and e[5].get("parent_id") == ids["span_id"])
    return seconds / n_fits


def read(ctx, span, minus=()):
    from skdist_tpu.obs import trace as obs_trace

    if not obs_trace.enabled() or obs_trace.dropped() > 0:
        return None
    return per_fit(obs_trace.events(), len(ctx["fits"]), span, tuple(minus))
