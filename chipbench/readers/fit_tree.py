"""What the first fit of a process pays, read from the program's span
trees: the metrics that move ``setup_s``.

Reads the program's ring (``skdist_tpu.obs.trace.events()``) in the
same process, after the window, as ``span_seconds`` does: the last
``len(ctx["fits"])`` ``search_fit`` roots are the window's, and THE ONE
BEFORE THEM IS THE WARM-UP FIT'S; a root's tree is every span that
carries its ``trace_id``. ``what`` picks the reading:

- ``outside_fit``: ``ctx["setup_s"]`` less the warm-up root — imports,
  the chip's start-up, the generator: the benchmark's and the host's
  share of set-up;
- ``first_fit_extra``: the warm-up root less the mean of the window's
  roots — what only the first fit of a process pays;
- ``warmup_compile``: seconds of the OUTERMOST of :data:`NAMED` in the
  warm-up tree — the named part of the line above. Outermost: a named
  span inside another named span (an ``xla_compile`` inside a
  ``compile``, an inner ``jit``'s ``jax_trace`` inside the outer one's)
  is counted once, so the seconds are those of the union of the named
  intervals, thread by thread;
- ``warmup_xla``: seconds of ``xla_compile`` in the warm-up tree — what
  XLA's persistent cache serves or the compiler costs;
- ``window_xla_compiles``: ``xla_compile`` spans in the window's trees
  a window fit — every backend compile of a steady fit, whatever path
  asked for it.

``None`` — the metric is left out — when tracing was off, when the
ring dropped events, when it holds fewer than the window's fits and
one more root, and, for the last three, on a program that does not
record what JAX reports of a compile (no span of :data:`ANNOUNCED`
anywhere in its ring)."""

ROOT = "search_fit"
#: what JAX announces of a compile, recorded by the program's listeners
ANNOUNCED = ("jax_trace", "jax_lower", "xla_compile")
#: the spans that name a part of a first fit's extra seconds
NAMED = ANNOUNCED + ("compile", "export_read", "export_write",
                     "lane_footprint")


def fit_trees(events, n_fits):
    """``[(root, tree), ...]`` of the warm-up fit and then the window's
    ``n_fits``, oldest first, or None. ``events``: the ring's ``(name,
    ph, t0, dur, tid, args)`` tuples."""
    spans = [e for e in events
             if e[1] == "X" and e[5] and e[5].get("trace_id")]
    roots = [e for e in spans if e[0] == ROOT]
    if n_fits < 1 or len(roots) < n_fits + 1:
        return None
    return [(root, [e for e in spans if e is not root
                    and e[5]["trace_id"] == root[5]["trace_id"]])
            for root in roots[-(n_fits + 1):]]


def outermost_seconds(tree, names=NAMED):
    """Seconds the spans of ``names`` cover in ``tree``, a nested one
    counted once: the union of their intervals on each thread."""
    by_thread = {}
    for e in tree:
        if e[0] in names:
            by_thread.setdefault(e[4], []).append((e[2], e[2] + e[3]))
    seconds = 0.0
    for intervals in by_thread.values():
        end = float("-inf")
        for t0, t1 in sorted(intervals):
            if t1 > end:
                seconds += t1 - max(t0, end)
                end = t1
    return seconds


def reading(events, n_fits, what, setup_s=None):
    trees = fit_trees(events, n_fits)
    if trees is None:
        return None
    (warm_root, warm_tree), window = trees[0], trees[1:]
    if what == "outside_fit":
        return setup_s - warm_root[3]
    if what == "first_fit_extra":
        return warm_root[3] - sum(root[3] for root, _ in window) / n_fits
    if not any(e[0] in ANNOUNCED for e in events):
        return None
    if what == "warmup_compile":
        return outermost_seconds(warm_tree)
    if what == "warmup_xla":
        return sum(e[3] for e in warm_tree if e[0] == "xla_compile")
    if what == "window_xla_compiles":
        return sum(e[0] == "xla_compile"
                   for _, tree in window for e in tree) / n_fits
    raise ValueError(f"fit_tree: no reading {what!r}")


def read(ctx, what):
    from skdist_tpu.obs import trace as obs_trace

    if not obs_trace.enabled() or obs_trace.dropped() > 0:
        return None
    return reading(obs_trace.events(), len(ctx["fits"]), what,
                   ctx.get("setup_s"))
