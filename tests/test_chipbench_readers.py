"""
The benchmark's readers of what the program measures inside a fit:
``round_counts`` (the round loop's counters), ``span_seconds`` (the
program's span tree), ``fit_tree`` (the warm-up fit's tree beside
the window's: what moves ``setup_s``) and ``compile_events`` (what JAX
reports of the process's compiles, from the registry), on hand-built
windows and hand-built ring events, and every metric file against the
reader and the ``BENCHMARK.json`` entry it needs.
"""

import glob
import importlib
import json
import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

from chipbench.readers import (  # noqa: E402
    compile_events, fit_tree, lanes_per_round, round_counts, span_seconds,
)
from skdist_tpu.obs import trace as obs_trace  # noqa: E402

#: the cell PR 35 adds: all of mnist8m, row-sharded over four chips
FULL_CELL = "search-mnist8m-full-4chip"

#: ... and the metric it brings
FOUR_CHIP_METRICS = ("collective_mb_per_program.search",)

NEW_METRICS = (
    "loss_evals_per_fit.search", "lbfgs_iters_per_fit.search",
    "live_lane_share_pct.search", "place_s_per_fit.search",
    "refit_s_per_fit.search", "search_host_s_per_fit.search",
    "lanes_per_round.search",
)


# ---------------------------------------------------------------------------
# round_counts
# ---------------------------------------------------------------------------

def _fit(stats, units=4, failed=0):
    return {"stats": stats, "units": units, "failed": failed}


def _ctx(fits):
    return {"fits": fits,
            "units_done": sum(f["units"] - f["failed"] for f in fits)}


COUNTED = {"iters": [10, 20, 30, 40], "fevals": [21, 45, 70, 100],
           "lane_slots": 16, "live_lane_slots": 12,
           "finalize": {"rounds": 1, "dispatch_s": 0.1}}


@pytest.mark.parametrize("args, want", [
    ({"key": "iters"}, 100 / 4),
    ({"key": "fevals"}, 236 / 4),
    ({"num": "live_lane_slots", "den": "lane_slots"}, 75.0),
])
def test_round_counts_one_fit(args, want):
    assert round_counts.read(_ctx([_fit(COUNTED)]), **args) == want


def test_round_counts_sum_over_fits_and_finalize_parts():
    second = dict(COUNTED, iters=[1, 1, 1, 1], lane_slots=8,
                  live_lane_slots=8,
                  finalize={"lane_slots": 8, "live_lane_slots": 0})
    ctx = _ctx([_fit(COUNTED), _fit(second)])
    assert round_counts.read(ctx, key="iters") == 104 / 8
    assert round_counts.read(
        ctx, num="live_lane_slots", den="lane_slots") == 100 * 20 / 32


@pytest.mark.parametrize("stats", [
    {"rounds": 3, "dispatch_s": 0.5},                      # the parent
    {"iters": None, "fevals": None, "lane_slots": None,
     "live_lane_slots": None},                             # no count_keys
    None,                                                  # the fit raised
])
def test_round_counts_nothing_to_read(stats):
    ctx = _ctx([_fit(stats)])
    assert round_counts.read(ctx, key="fevals") is None
    assert round_counts.read(
        ctx, num="live_lane_slots", den="lane_slots") is None


#: the look-ahead's share of the slice loop's dispatches (PR 38)
AHEAD_METRICS = ("slices_ahead_pct.search",)


def test_slices_ahead_metric_resolves_to_the_reader_and_an_entry():
    name, = AHEAD_METRICS
    with open(os.path.join(REPO, "chipbench", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    # the denominator is the slice loop's dispatches, which the
    # finalize part does not book (its ``rounds`` would be summed in)
    assert spec == {"reader": "round_counts",
                    "args": {"num": "slices_ahead",
                             "den": "rounds_per_slice"}}
    entry = {m["name"]: m for m in _bench()["per_layer"]}[name]
    # no ``workloads``: every cell reports it, 0 where the loop bypasses
    assert entry == {"name": name, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "round dispatch",
                     "moves": "search_fits_per_s"}


@pytest.mark.parametrize("fits, want", [
    # a lone round's 8 dispatches, 7 enqueued ahead; the finalize part's
    # rounds are not the denominator's
    ([dict(COUNTED, slices_ahead=7, rounds_per_slice=[2, 1, 1, 1, 1, 1, 1])],
     100 * 7 / 8),
    # summed over the fits: 13 of 16 dispatches
    ([dict(COUNTED, slices_ahead=7, rounds_per_slice=[2, 1, 1, 1, 1, 1, 1]),
      dict(COUNTED, slices_ahead=6, rounds_per_slice=[2, 2, 1, 1, 1, 1])],
     100 * 13 / 16),
    # the bypass: the flags read in step
    ([dict(COUNTED, slices_ahead=0, rounds_per_slice=[1, 1, 1, 1])], 0.0),
    # a program without the counter: the metric is left out
    ([dict(COUNTED, rounds_per_slice=[1, 1, 1, 1])], None),
    ([{"rounds": 3, "dispatch_s": 0.5}], None),
    ([None], None),
])
def test_slices_ahead_pct(fits, want):
    ctx = _ctx([_fit(stats) for stats in fits])
    assert round_counts.read(ctx, num="slices_ahead",
                             den="rounds_per_slice") == want


@pytest.mark.parametrize("stats, want", [
    (dict(COUNTED, rounds=2), 8.0),            # finalize's round left out
    ({"lane_slots": 378, "rounds": 54}, 7.0),  # the parent: rounds of 7
    ({"rounds": 3, "dispatch_s": 0.5}, None),  # a program without it
    ({"lane_slots": None, "rounds": 3}, None),
    (None, None),
])
def test_lanes_per_round(stats, want):
    assert lanes_per_round.read(_ctx([_fit(stats)])) == want


def test_round_counts_no_unit_completed():
    ctx = _ctx([_fit(COUNTED, units=4, failed=4)])
    assert round_counts.read(ctx, key="iters") is None


# ---------------------------------------------------------------------------
# span_seconds
# ---------------------------------------------------------------------------

def _span(name, t0, dur, trace, span, parent):
    return (name, "X", float(t0), float(dur), 1,
            {"trace_id": trace, "span_id": span, "parent_id": parent})


def _tree(trace, t0, scale=1.0):
    """One fit's ring events, children before their parent (the ring
    appends a span when it exits). Durations times ``scale``: root 10,
    cv_split 0.5, place_shared 1, round_loop 5 (holding a dispatch and
    a wait), finalize 1, refit 2 (holding a nested place_shared of
    0.5): 0.5 of the root is nobody's."""
    root = trace + "-root"
    s = scale

    def sp(name, at, dur, span, parent=root):
        return _span(name, t0 + at * s, dur * s, trace, trace + span,
                     parent)

    return [
        sp("cv_split", 0, 0.5, "-a"),
        sp("place_shared", 0.5, 1, "-b"),
        sp("round_dispatch", 1.5, 0.1, "-c1", trace + "-c"),
        sp("flags_wait", 1.6, 4.9, "-c2", trace + "-c"),
        sp("round_loop", 1.5, 5, "-c"),
        sp("finalize", 6.5, 1, "-d"),
        sp("place_shared", 7.5, 0.5, "-e1", trace + "-e"),
        sp("refit", 7.5, 2, "-e"),
        _span("search_fit", t0, 10 * s, trace, root, trace + "-caller"),
    ]


MINUS = ("place_shared", "round_loop", "finalize", "refit")


def test_span_seconds_one_root():
    events = _tree("t1", 100.0)
    assert span_seconds.per_fit(events, 1, "search_fit") == 10.0
    assert span_seconds.per_fit(events, 1, "refit") == 2.0
    # summed over the whole tree: the refit's nested placement counts
    assert span_seconds.per_fit(events, 1, "place_shared") == 1.5
    # ... but is taken off the root once, inside `refit`
    assert span_seconds.per_fit(
        events, 1, "search_fit", MINUS) == pytest.approx(1.0)


def test_span_seconds_skips_the_warm_up_fit():
    loose = ("compile", "X", 50.0, 3.0, 1, {"tier": "aot"})
    instant = ("lane_retire", "i", 120.0, 0.0, 1,
               {"trace_id": "t2", "parent_id": "t2-c"})
    events = ([loose] + _tree("warm", 0.0, scale=3.0) + _tree("t1", 100.0)
              + [instant] + _tree("t2", 200.0, scale=2.0))
    assert span_seconds.per_fit(events, 2, "refit") == (2.0 + 4.0) / 2
    assert span_seconds.per_fit(events, 1, "refit") == 4.0
    assert span_seconds.per_fit(events, 3, "refit") == (6 + 2 + 4) / 3
    assert span_seconds.per_fit(
        events, 2, "search_fit", MINUS) == pytest.approx(1.5)


def test_span_seconds_too_few_roots():
    events = _tree("t1", 0.0)
    assert span_seconds.per_fit(events, 2, "refit") is None
    assert span_seconds.per_fit([], 1, "refit") is None
    assert span_seconds.per_fit(events, 0, "refit") is None
    # the parent's ring: its loose spans carry no ids and name no root
    parent = [("round_dispatch", "X", 1.0, 0.1, 1, None),
              ("compile", "X", 2.0, 0.1, 1, {"tier": "jit"})]
    assert span_seconds.per_fit(parent, 1, "place_shared") is None


@pytest.fixture
def ring():
    """The program's ring, enabled and empty, with what the test
    appends; restored to off and empty."""
    obs_trace.set_enabled(True)
    obs_trace.clear()
    yield obs_trace._append
    obs_trace.set_enabled(False)
    obs_trace.set_ring_size(65536)


def test_span_seconds_reads_the_programs_ring(ring):
    for ev in _tree("warm", 0.0) + _tree("t1", 100.0, scale=2.0):
        ring(ev)
    ctx = {"fits": [{}]}
    assert span_seconds.read(ctx, span="refit") == 4.0
    assert span_seconds.read(
        ctx, span="search_fit", minus=list(MINUS)) == pytest.approx(2.0)
    assert span_seconds.read({"fits": [{}, {}, {}]}, span="refit") is None


def test_span_seconds_none_when_tracing_is_off(ring):
    for ev in _tree("t1", 0.0):
        ring(ev)
    obs_trace.set_enabled(False)
    assert span_seconds.read({"fits": [{}]}, span="refit") is None


def test_span_seconds_none_on_a_dropped_ring(ring):
    obs_trace.set_ring_size(9)
    for ev in _tree("warm", 0.0) + _tree("t1", 100.0):
        ring(ev)
    assert obs_trace.dropped() == 9
    assert len(obs_trace.events()) == 9  # t1's tree is whole
    assert span_seconds.read({"fits": [{}]}, span="refit") is None


def test_span_seconds_on_a_real_traced_fit(ring):
    """The reader against the program itself: the three span metrics of
    one small traced search add up inside its root."""
    import jax
    import numpy as np

    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.parallel import TPUBackend

    rng = np.random.RandomState(0)
    X = rng.normal(size=(300, 8)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int64)
    backend = TPUBackend(devices=jax.devices()[:1])
    for _ in range(2):  # a warm-up fit, then the window's
        DistGridSearchCV(
            LogisticRegression(max_iter=40, engine="xla"),
            {"C": [float(c) for c in np.logspace(-2, 2, 8)]},
            backend=backend, cv=4,
        ).fit(X, y)
    stats = dict(backend.last_round_stats)
    ctx = {"fits": [{"stats": stats, "units": 32, "failed": 0}],
           "units_done": 32}
    root = span_seconds.read(ctx, span="search_fit")
    parts = [span_seconds.read(ctx, span="place_shared"),
             span_seconds.read(ctx, span="refit"),
             span_seconds.read(ctx, span="search_fit", minus=list(MINUS))]
    assert all(p is not None and p > 0 for p in parts)
    assert sum(parts) < root
    assert round_counts.read(ctx, key="iters") <= 40
    assert round_counts.read(ctx, key="fevals") >= (
        2 * round_counts.read(ctx, key="iters") + 1)
    assert 0 < round_counts.read(
        ctx, num="live_lane_slots", den="lane_slots") <= 100
    assert lanes_per_round.read(ctx) == stats["chunk"]


# ---------------------------------------------------------------------------
# fit_tree: the warm-up fit's tree beside the window's (PR 37)
# ---------------------------------------------------------------------------

#: name -> (``what`` the reader is asked for, source, layer, moves), in
#: the order the entries were appended
SETUP_METRICS = {
    "setup_outside_fit_s.setup":
        ("outside_fit", "host_clock", "process start", "setup_s"),
    "first_fit_extra_s.setup":
        ("first_fit_extra", "program_span", "compile", "setup_s"),
    "warmup_compile_s.setup":
        ("warmup_compile", "program_span", "compile", "setup_s"),
    "warmup_xla_s.setup":
        ("warmup_xla", "program_span", "compile", "setup_s"),
    "window_xla_compiles.search":
        ("window_xla_compiles", "program_span", "compile",
         "search_fits_per_s"),
}


def _first_tree(trace, t0):
    """``_tree`` with what a process's first fit compiles, the root 16
    long for it: under ``round_loop`` a ``compile`` of 4 (aot) holding
    an ``export_write`` of 1.5 — itself holding a ``jax_trace`` of 1
    with an inner ``jit``'s ``jax_trace`` of 0.4 inside that — a
    ``jax_lower`` of 0.5 and an ``xla_compile`` of 2; under ``refit``,
    outside every ``compile``, a ``jax_trace`` of 0.3, a ``jax_lower``
    of 0.2 and an ``xla_compile`` of 1; a ``lane_footprint`` of 0.5
    under the root with a ``jax_trace`` of 0.45 in it. Spans that JAX
    reports carry their enclosing span's id as parent, nested or not.
    Named and outermost: 4 + 0.3 + 0.2 + 1 + 0.5 = 6."""
    root = trace + "-root"

    def sp(name, at, dur, span, parent=root, **args):
        e = _span(name, t0 + at, dur, trace, trace + span, parent)
        e[5].update(args)
        return e

    loop, aot, write, refit = (trace + s for s in ("-c", "-k", "-w", "-e"))
    return [
        sp("cv_split", 0, 0.5, "-a"),
        sp("jax_trace", 0.52, 0.45, "-f1", trace + "-f", fun="init"),
        sp("lane_footprint", 0.5, 0.5, "-f"),
        sp("place_shared", 1.0, 1, "-b"),
        sp("jax_trace", 2.3, 0.4, "-w2", write, fun="inner"),
        sp("jax_trace", 2.1, 1.0, "-w1", write, fun="step"),
        sp("export_write", 2.0, 1.5, "-w", aot, bytes=1000),
        sp("jax_lower", 3.5, 0.5, "-k1", aot, fun="jit(call)"),
        sp("xla_compile", 4.0, 2.0, "-k2", aot, fun="jit(call)",
           cache="miss"),
        sp("compile", 2.0, 4.0, "-k", loop, tier="aot", export="write"),
        sp("round_loop", 2.0, 9.0, "-c"),
        sp("finalize", 11.0, 1, "-d"),
        sp("jax_trace", 12.0, 0.3, "-e1", refit, fun="kernel"),
        sp("jax_lower", 12.3, 0.2, "-e2", refit, fun="jit(kernel)"),
        sp("xla_compile", 12.5, 1.0, "-e3", refit, fun="jit(kernel)",
           cache="hit"),
        sp("refit", 12.0, 3.5, "-e"),
        _span("search_fit", t0, 16.0, trace, root, trace + "-caller"),
    ]


def _process(n_window=2):
    """A process's ring: something JAX compiled before any fit (no
    ids), the warm-up fit, then ``n_window`` steady fits of 10."""
    loose = ("xla_compile", "X", 1.0, 7.0, 1,
             {"fun": "jit(generate)", "cache": "miss"})
    events = [loose] + _first_tree("warm", 20.0)
    for i in range(n_window):
        events += _tree(f"t{i}", 100.0 + 20 * i)
    return events


@pytest.mark.parametrize("what, want", [
    ("outside_fit", 40.0 - 16.0),
    ("first_fit_extra", 16.0 - 10.0),
    ("warmup_compile", 6.0),
    ("warmup_xla", 3.0),
    ("window_xla_compiles", 0.0),
])
def test_fit_tree_readings(what, want):
    assert fit_tree.reading(_process(), 2, what, setup_s=40.0) == \
        pytest.approx(want)


def test_fit_tree_finds_the_warm_up_root_before_the_windows():
    events = _tree("older", 0.0, scale=5.0) + _process(3)
    trees = fit_tree.fit_trees(events, 3)
    assert [root[5]["trace_id"] for root, _ in trees] == [
        "warm", "t0", "t1", "t2"]
    assert all(e[5]["trace_id"] == "warm" for e in trees[0][1])
    assert len(trees[0][1]) == len(_first_tree("warm", 0.0)) - 1
    # the window's mean is over the window's roots alone
    slow = _process(1) + _tree("t1", 200.0, scale=1.4)
    assert fit_tree.reading(slow, 2, "first_fit_extra") == \
        pytest.approx(16.0 - 12.0)


@pytest.mark.parametrize("names, want", [
    (("compile", "xla_compile"), 4.0 + 1.0),   # one inside, one outside
    (("jax_trace",), 0.45 + 1.0 + 0.3),        # the inner jit's once
    (("export_write", "jax_trace"), 0.45 + 1.5 + 0.3),
    (("xla_compile",), 3.0),
    (("pack_x",), 0.0),
])
def test_fit_tree_counts_a_nested_span_once(names, want):
    tree = _first_tree("warm", 0.0)
    assert fit_tree.outermost_seconds(tree, names) == pytest.approx(want)


def test_fit_tree_counts_threads_apart():
    """Two threads that compile at the same time each paid their
    seconds; one thread's overlapping reports are one interval."""
    a = ("xla_compile", "X", 0.0, 2.0, 1, {})
    b = ("xla_compile", "X", 1.0, 2.0, 2, {})
    c = ("jax_lower", "X", 1.5, 1.0, 1, {})
    assert fit_tree.outermost_seconds([a, b, c]) == pytest.approx(2.5 + 2.0)


def test_fit_tree_window_compiles_are_counted_per_fit():
    events = _process(2)
    recompiled = _span("xla_compile", 105.0, 0.2, "t0", "t0-x", "t0-e")
    recompiled[5].update(fun="jit(kernel)", cache="hit")
    assert fit_tree.reading(events + [recompiled], 2,
                            "window_xla_compiles") == 0.5


@pytest.mark.parametrize("what", [m[0] for m in SETUP_METRICS.values()])
def test_fit_tree_too_few_roots(what):
    events = _process(2)
    assert fit_tree.reading(events, 3, what, setup_s=40.0) is None
    assert fit_tree.reading(events, 0, what, setup_s=40.0) is None
    assert fit_tree.reading([], 1, what, setup_s=40.0) is None


@pytest.mark.parametrize("what, want", [
    ("outside_fit", 30.0), ("first_fit_extra", 0.0),
    ("warmup_compile", None), ("warmup_xla", None),
    ("window_xla_compiles", None),
])
def test_fit_tree_on_a_program_that_does_not_report_its_compiles(
        what, want):
    """The parent's ring: roots and ``compile`` spans, nothing of what
    JAX announces — the roots' two readings, and no number made up for
    the rest."""
    loose = ("compile", "X", 1.0, 0.1, 1, {"tier": "jit"})
    events = [loose] + _tree("warm", 0.0) + _tree("t1", 100.0)
    got = fit_tree.reading(events, 1, what, setup_s=40.0)
    assert got == want if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("what", [m[0] for m in SETUP_METRICS.values()])
def test_fit_tree_none_when_tracing_is_off_or_the_ring_dropped(ring, what):
    for ev in _process(1):
        ring(ev)
    ctx = {"fits": [{}], "setup_s": 40.0}
    assert fit_tree.read(ctx, what) is not None
    obs_trace.set_enabled(False)
    assert fit_tree.read(ctx, what) is None
    obs_trace.set_enabled(True)
    obs_trace.set_ring_size(len(_process(1)) - 1)
    for ev in _process(1):
        ring(ev)
    assert obs_trace.dropped() == 1
    assert fit_tree.read(ctx, what) is None


def test_fit_tree_on_real_traced_fits(ring):
    """The reader against the program itself: a first fit whose memos
    were cleared, then a steady one."""
    import jax
    import numpy as np

    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.parallel import TPUBackend, compile_cache

    rng = np.random.RandomState(1)
    X = rng.normal(size=(300, 9)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int64)
    backend = TPUBackend(devices=jax.devices()[:1])
    compile_cache.clear_memos()
    for _ in range(2):
        DistGridSearchCV(
            LogisticRegression(max_iter=40, engine="xla"),
            {"C": [float(c) for c in np.logspace(-2, 2, 8)]},
            backend=backend, cv=4, partitions=8,
        ).fit(X, y)
    compile_cache.clear_memos()
    roots = [e for e in obs_trace.events() if e[0] == "search_fit"]
    ctx = {"fits": [{}], "setup_s": roots[0][3] + 5.0}
    got = {what: fit_tree.read(ctx, what)
           for what, *_ in SETUP_METRICS.values()}
    assert got["outside_fit"] == pytest.approx(5.0)
    assert got["first_fit_extra"] == pytest.approx(roots[0][3] - roots[1][3])
    assert 0 < got["warmup_xla"] <= got["warmup_compile"] <= roots[0][3]
    assert got["warmup_compile"] <= got["first_fit_extra"] + roots[1][3]
    assert got["window_xla_compiles"] == 0.0


@pytest.mark.parametrize("name", sorted(SETUP_METRICS))
def test_setup_metric_resolves_to_the_reader_and_an_entry(name):
    what, source, layer, moves = SETUP_METRICS[name]
    with open(os.path.join(REPO, "chipbench", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec == {"reader": "fit_tree", "args": {"what": what}}
    entry = {m["name"]: m for m in _bench()["per_layer"]}[name]
    # no ``workloads``: every cell reports them
    assert entry == {"name": name, "unit": entry["unit"], "better": "lower",
                     "source": source, "layer": layer, "moves": moves}
    assert entry["unit"] == ("count" if what == "window_xla_compiles"
                             else "s")
    assert name.endswith(".setup" if moves == "setup_s" else ".search")
    # the reader takes the file's arguments, and finds nothing to read
    # on a ring without the roots
    assert fit_tree.read({"fits": [{}], "setup_s": 1.0},
                         **spec["args"]) is None


# ---------------------------------------------------------------------------
# compile_events: the registry's counts of what JAX reports (PR 37)
# ---------------------------------------------------------------------------

#: name -> the ``snapshot()`` key it reads, in the order appended
COUNTER_METRICS = {
    "backend_compiles.setup": "backend_compiles",
    "xla_cache_misses.setup": "xla_cache_misses",
}


@pytest.mark.parametrize("name", sorted(COUNTER_METRICS))
def test_counter_metric_resolves_to_the_reader_and_an_entry(name):
    key = COUNTER_METRICS[name]
    with open(os.path.join(REPO, "chipbench", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec == {"reader": "compile_events", "args": {"key": key}}
    entry = {m["name"]: m for m in _bench()["per_layer"]}[name]
    # no ``workloads``: every cell reports them, traced or not
    assert entry == {"name": name, "unit": "count", "better": "lower",
                     "source": "program_counter", "layer": "compile",
                     "moves": "setup_s"}


def test_compile_events_reads_the_process_total(monkeypatch):
    """Untraced too: the registry counts whether the ring records or
    not, and a compile XLA's cache serves is no miss."""
    import jax
    import numpy as np

    from skdist_tpu.parallel import compile_cache

    compile_cache.enable_disk_cache()
    assert not obs_trace.enabled()
    before = {k: compile_events.read({}, k)
              for k in COUNTER_METRICS.values()}
    assert all(isinstance(v, float) for v in before.values())

    def fn(x):
        return x * 7 - 3

    jax.jit(fn)(np.float32(2)).block_until_ready()
    assert compile_events.read({}, "backend_compiles") == \
        before["backend_compiles"] + 1
    jax.clear_caches()
    jax.jit(fn)(np.float32(2)).block_until_ready()  # read from the disk
    assert compile_events.read({}, "backend_compiles") == \
        before["backend_compiles"] + 2
    assert compile_events.read({}, "xla_cache_misses") <= \
        before["xla_cache_misses"] + 1


def test_compile_events_on_a_program_without_the_counters(monkeypatch):
    """The parent's ``snapshot()`` has no such key: the metric is left
    out, nothing raises."""
    from skdist_tpu.parallel import compile_cache

    monkeypatch.setattr(compile_cache, "snapshot",
                        lambda: {"aot_misses": 3, "disk_cache_dir": "/x"})
    for key in COUNTER_METRICS.values():
        assert compile_events.read({}, key) is None


# ---------------------------------------------------------------------------
# the metric files
# ---------------------------------------------------------------------------

def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_resolves_to_a_reader_and_an_entry(name):
    with open(os.path.join(REPO, "chipbench", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("chipbench.readers." + spec["reader"])
    assert reader in (round_counts, span_seconds, lanes_per_round)
    entry = {m["name"]: m for m in _bench()["per_layer"]}[name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # the dense cells: the multiclass one joined them (PR 32), and the
    # whole of it on four chips (PR 35)
    assert entry["workloads"] == ["search-epsilon", "search-mnist8m",
                                  FULL_CELL]
    assert entry["moves"] == "search_fits_per_s"
    assert entry["source"] == ("program_span" if reader is span_seconds
                               else "program_counter")
    # the reader takes the file's arguments, and finds nothing to read
    # in a window of a program that measures none of this
    empty = {"fits": [{"stats": {"rounds": 1}, "units": 1, "failed": 0}],
             "units_done": 1}
    assert reader.read(empty, **spec.get("args", {})) is None


def test_every_metric_file_has_its_entry_and_reader():
    bench = _bench()
    named = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    files = glob.glob(os.path.join(REPO, "chipbench", "metrics", "*.json"))
    assert {os.path.basename(p)[:-len(".json")] for p in files} == named
    for path in files:
        with open(path) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "readers", spec["reader"] + ".py"))
    # the new entries were appended: the accepted ones keep their places
    appended = (NEW_METRICS + TEXT_METRICS + MNIST_METRICS
                + FOUR_CHIP_METRICS + tuple(SETUP_METRICS)
                + tuple(COUNTER_METRICS) + AHEAD_METRICS)
    assert [m["name"] for m in bench["per_layer"]][
        -len(appended):] == list(appended)


# ---------------------------------------------------------------------------
# the text cell's four (PR 28)
# ---------------------------------------------------------------------------

#: name -> (reader module, source) of the metrics ``search-20news130k``
#: brought
TEXT_METRICS = (
    "lbfgs_sparse_mfu_pct.search", "packed_fill_pct.search",
    "pack_s_per_fit.search", "round_mem_estimate_pct.search",
)


#: the three ``search-mnist8m`` brought (PR 32)
MNIST_METRICS = (
    "logits_share_of_lane_pct.search", "round_retries_per_fit.search",
    "round_mem_vs_compiled_pct.search",
)


@pytest.mark.parametrize("name, reader, source", zip(
    TEXT_METRICS,
    ("work_share", "round_counts", "span_seconds", "round_mem_estimate"),
    ("host_clock", "program_counter", "program_span", "program_counter")))
def test_text_cell_metric_resolves_to_a_reader_and_an_entry(
        name, reader, source):
    with open(os.path.join(REPO, "chipbench", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == reader
    entry = {m["name"]: m for m in _bench()["per_layer"]}[name]
    # round sizing's estimate is read in every cell whose rounds memory
    # sizes: the dense multiclass one joined it (PR 32)
    assert entry["workloads"] == ["search-20news130k"] + (
        ["search-mnist8m"] if reader == "round_mem_estimate" else [])
    assert (entry["moves"], entry["source"]) == ("search_fits_per_s", source)
    if reader != "work_share":
        # nothing to read in a window of a program without the counter
        # or the span (the parent commit): the metric is left out
        empty = {"fits": [{"stats": {"rounds": 1}, "units": 1,
                           "failed": 0}],
                 "units_done": 1, "memory_peak_bytes": 1 << 30}
        module = importlib.import_module("chipbench.readers." + reader)
        assert module.read(empty, **spec.get("args", {})) is None


def test_dense_share_of_the_peak_does_not_read_the_text_cell():
    entry = {m["name"]: m for m in _bench()["per_layer"]}[
        "lbfgs_mfu_pct.search"]
    # the dense cells, binary and multinomial (its work function counts
    # ``k`` columns; on four chips the share is of four chips' peak:
    # ``work_share`` divides by the chips used), and never the packed one
    assert entry["workloads"] == ["search-epsilon", "search-mnist8m",
                                  FULL_CELL]


@pytest.mark.parametrize("stats, peak, want", [
    # 0.7 GB shared and ten lanes of 0.67 GB against 9.1 GB held
    ({"chunk": 10, "lane_bytes": 670_000_000,
      "shared_bytes": 700_000_000}, 9_100_000_000, 100 * 7.4 / 9.1),
    ({"chunk": 10, "lane_bytes": None, "shared_bytes": 7}, 1 << 30, None),
    ({"chunk": 10, "lane_bytes": 5, "shared_bytes": 7}, 0, None),
    ({"rounds": 3}, 1 << 30, None),
])
def test_round_mem_estimate(stats, peak, want):
    from chipbench.readers import round_mem_estimate

    got = round_mem_estimate.read(
        {"fits": [_fit(stats)], "memory_peak_bytes": peak})
    assert got == (want if want is None else pytest.approx(want))


def test_packed_fill_reads_the_booked_counts():
    stats = {"x_nnz": 2_175_334, "x_slots": 173_281_098, "rounds": 5}
    got = round_counts.read(_ctx([_fit(stats), _fit(stats)]),
                            num="x_nnz", den="x_slots")
    assert got == pytest.approx(1.2553787)


@pytest.mark.parametrize("stats, units, want", [
    ({"retries": 0, "refused": 0}, 50, 0.0),
    ({"retries": 2, "refused": 1, "finalize": {"retries": 1}}, 50, 4 / 50),
    # a program that books no refusals (the parent): its retries alone
    ({"retries": 3}, 50, 3 / 50),
    ({"rounds": 4}, 50, None),
    ({"retries": 1, "refused": 1}, 0, None),
])
def test_round_retries_sums_what_was_dispatched_again(stats, units, want):
    from chipbench.readers import round_retries

    got = round_retries.read(
        {"fits": [_fit(stats)], "units_done": units},
        keys=["retries", "refused"])
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("name, num, den", [
    ("logits_share_of_lane_pct.search", "logits_bytes", "lane_bytes"),
    ("round_mem_vs_compiled_pct.search", "round_bytes_estimate",
     "round_bytes_compiled"),
])
def test_mnist_cell_share_metrics_read_the_booked_bytes(name, num, den):
    """The two shares PR 32 brings ride ``round_counts``: 100 x the
    booked numerator over the booked denominator, nothing on a program
    that books neither; each lists the cell and moves the rate."""
    from chipbench import run
    from chipbench.readers import round_counts

    spec = run.load_json("chipbench", "metrics", name + ".json")
    assert spec == {"reader": "round_counts",
                    "args": {"num": num, "den": den}}
    entry = {m["name"]: m for m in _bench()["per_layer"]}[name]
    assert entry["workloads"] == ["search-mnist8m", FULL_CELL]
    assert entry["moves"] == "search_fits_per_s"
    ctx = {"fits": [_fit({num: 416, den: 417})], "units_done": 50}
    assert round_counts.read(ctx, **spec["args"]) == pytest.approx(
        100 * 416 / 417)
    ctx = {"fits": [_fit({"rounds": 3})], "units_done": 50}
    assert round_counts.read(ctx, **spec["args"]) is None


# ---------------------------------------------------------------------------
# the four-chip cell's own (PR 35)
# ---------------------------------------------------------------------------

def _four_chip_entry(name):
    from chipbench import run

    entry = {m["name"]: m for m in _bench()["per_layer"]}[name]
    assert entry["workloads"] == [FULL_CELL]
    assert entry["moves"] == "search_fits_per_s"
    return run.load_json("chipbench", "metrics", name + ".json"), entry


@pytest.mark.parametrize("busy, want", [
    ({"d0": 10.0, "d1": 9.0, "d2": 9.5, "d3": 10.0}, 10.0),
    ({"d0": 8.0, "d1": 2.0}, 75.0),
    ({"d0": 4.0, "d1": 4.0, "d2": 4.0, "d3": 4.0}, 0.0),
    # one device: nothing to spread; no device busy; no trace at all
    ({"d0": 10.0}, None),
    ({"d0": 0.0, "d1": 0.0}, None),
    (None, None),
])
def test_shard_busy_spread_reads_the_traced_devices(busy, want):
    """The reader is here and no metric reads it yet: the cell's
    traffic traces the first 12 s of a fit, which hold placement only,
    so ``shard_busy_spread_pct.search`` waits for a traffic whose
    trace begins past placement (PERF.md section 7)."""
    from chipbench.readers import shard_busy_spread

    assert "shard_busy_spread_pct.search" not in {
        m["name"] for m in _bench()["per_layer"]}
    trace = None if busy is None else {
        "window_s": 12.0, "busy_s": 1.0, "busy_s_per_device": busy}
    got = shard_busy_spread.read({"trace": trace})
    assert got == (want if want is None else pytest.approx(want))
    # a trace reduced by a harness that kept no per-device seconds
    assert shard_busy_spread.read(
        {"trace": {"window_s": 1.0, "busy_s": 0.5}}) is None


@pytest.mark.parametrize("stats, want", [
    ([{"collective_bytes_compiled": 408_304}], 0.408304),
    # the mean over the fits that hold it: each books its step program's
    ([{"collective_bytes_compiled": 400_000},
      {"collective_bytes_compiled": 600_000}, {"rounds": 4}], 0.5),
    ([{"collective_bytes_compiled": 0}], 0.0),
    # a program without the counter (the parent), or one that could not
    # read its program's text
    ([{"rounds": 4, "round_bytes_compiled": 1 << 30}], None),
    ([{"collective_bytes_compiled": None}], None),
    ([None], None),
])
def test_collective_mb_reads_what_the_step_program_was_compiled_with(
        stats, want):
    from chipbench.readers import round_stat_mean

    spec, entry = _four_chip_entry("collective_mb_per_program.search")
    assert spec == {"reader": "round_stat_mean",
                    "args": {"key": "collective_bytes_compiled",
                             "scale": 1e-06}}
    assert (entry["source"], entry["layer"], entry["unit"]) == (
        "program_counter", "round dispatch", "MB")
    got = round_stat_mean.read(
        {"fits": [_fit(s) for s in stats], "units_done": 50},
        **spec["args"])
    assert got == (want if want is None else pytest.approx(want))
