"""
Unified telemetry plane tests (``skdist_tpu.obs``):

- registry: thread-safety under concurrent labeled increments, family
  kind stickiness, histogram percentile correctness vs numpy;
- trace: span nesting/ordering, Chrome trace-event schema validity of
  the export, ring-buffer bounding, and the SKDIST_TRACE=0 contract —
  the disabled hot path records nothing and allocates nothing;
- views: faults/compile_cache snapshot() read the registry, scoped
  compile attribution separates one engine's misses from concurrent
  work, and every dispatch path's ``last_round_stats`` carries the
  converged RoundStats key set (regression-pinned per path).
"""

import json
import threading
import time

import numpy as np
import pytest

from skdist_tpu.obs import export as obs_export
from skdist_tpu.obs import metrics as obs_metrics
from skdist_tpu.obs import trace as obs_trace
from skdist_tpu.obs.metrics import (
    ROUND_STATS_REQUIRED,
    MetricsRegistry,
    new_round_stats,
)


def _span(name, args=None):
    with obs_trace.span(name, args):
        pass


#: the three ways an event reaches the ring
RECORDERS = {
    "span": _span,
    "instant": obs_trace.instant,
    "complete": lambda name, args=None: obs_trace.complete(
        name, 1.0, 0.5, args),
}


@pytest.fixture
def tracing():
    """Tracing ON with a fresh ring; restores the disabled default."""
    obs_trace.clear()
    prev = obs_trace.set_enabled(True)
    yield
    obs_trace.set_enabled(False)
    obs_trace.clear()
    assert prev is True  # set_enabled returned the NEW state


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_gauge_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("x.count")
        c.inc()
        c.inc(4, model="m@1")
        assert c.get() == 1
        assert c.get(model="m@1") == 4
        assert c.total() == 5
        g = reg.gauge("x.depth")
        g.set(7, q="a")
        g.set(3, q="b")
        assert g.get(q="a") == 7
        g.inc(2, q="a")
        assert g.get(q="a") == 9

    def test_kind_stickiness(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_help_upgrades_from_empty_only(self):
        """A bare ``counter(name)`` peek must not strip the HELP line
        off the family's real registration site (the fleet exposition
        conformance tests read HELP through the harvest merge) — but
        the first NON-empty help stays sticky."""
        reg = MetricsRegistry()
        fam = reg.counter("x.peeked")      # ad-hoc read, no help
        assert fam.help == ""
        reg.counter("x.peeked", help="the real help")
        assert fam.help == "the real help"
        reg.counter("x.peeked", help="a later, different help")
        assert fam.help == "the real help"

    def test_thread_safety_concurrent_increments(self):
        """N threads x M increments over shared label children land
        exactly N*M — the lost-update test a bare dict += fails."""
        reg = MetricsRegistry()
        c = reg.counter("t.events")
        h = reg.histogram("t.lat", buckets=(0.5, 1.0))
        n_threads, n_inc = 8, 2000

        def worker(i):
            for k in range(n_inc):
                c.inc(1, kind="shared")
                c.inc(1, kind=f"own-{i}")
                h.observe(0.25)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.get(kind="shared") == n_threads * n_inc
        for i in range(n_threads):
            assert c.get(kind=f"own-{i}") == n_inc
        count, total = h.get()
        assert count == n_threads * n_inc
        assert total == pytest.approx(0.25 * count)

    def test_histogram_percentiles_match_numpy(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", window=8192)
        rng = np.random.RandomState(7)
        samples = rng.lognormal(-3, 1.2, size=3000)
        for s in samples:
            h.observe(float(s))
        for q in (0, 10, 50, 90, 99, 100):
            np.testing.assert_allclose(
                h.percentile(q), np.percentile(samples, q), rtol=1e-12
            )

    def test_histogram_window_rolls(self):
        """Percentiles read the bounded ring (recent behaviour), while
        bucket counts/sum stay cumulative."""
        reg = MetricsRegistry()
        h = reg.histogram("lat", window=100)
        for _ in range(500):
            h.observe(1.0)
        for _ in range(100):
            h.observe(5.0)
        assert h.percentile(50) == 5.0  # ring holds only the tail
        count, total = h.get()
        assert count == 600 and total == pytest.approx(1000.0)

    def test_histogram_bucket_semantics(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 0.5, 7.0):
            h.observe(v)
        child = h.children()[()]
        assert child["counts"] == [1, 2, 1]  # <=0.1, <=1.0, +Inf

    def test_reset_prefix(self):
        reg = MetricsRegistry()
        reg.counter("a.x").inc(3)
        reg.counter("b.x").inc(5)
        reg.reset("a.")
        assert reg.counter("a.x").get() == 0
        assert reg.counter("b.x").get() == 5


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

_PROM_SAMPLE = (
    r'^[a-zA-Z_][a-zA-Z0-9_]*(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})? -?[0-9.e+-]+(inf)?$'
)


def test_prometheus_exposition_parses():
    import re

    reg = MetricsRegistry()
    reg.counter("compile.events").inc(3, kind="aot_misses")
    reg.gauge("serve.queue_depth").set(2, engine="serve-0")
    h = reg.histogram("serve.latency_s", buckets=(0.001, 0.01))
    h.observe(0.002, model="m@1")
    text = obs_export.prometheus_text(reg)
    assert text.endswith("\n")
    sample_re = re.compile(_PROM_SAMPLE)
    n_samples = 0
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            parts = line.split()
            assert parts[3] in ("counter", "gauge", "histogram")
            continue
        assert sample_re.match(line), f"bad exposition line: {line!r}"
        n_samples += 1
    # counter sample + gauge sample + 3 buckets + sum + count
    assert n_samples == 1 + 1 + 3 + 1 + 1
    # histogram le buckets are cumulative and end at +Inf == count
    assert 'le="+Inf"' in text


def test_json_snapshot_roundtrips(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a.b").inc(2, k="v")
    path = tmp_path / "snap.json"
    snap = obs_export.json_snapshot(reg, path=str(path))
    loaded = json.loads(path.read_text())
    assert loaded == snap
    assert loaded["a.b"]["kind"] == "counter"
    assert loaded["a.b"]["values"] == {"k=v": 2}


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------

class TestTrace:
    def test_span_nesting_and_ordering(self, tracing):
        with obs_trace.span("outer"):
            with obs_trace.span("inner_a"):
                pass
            with obs_trace.span("inner_b"):
                pass
        evs = {e[0]: e for e in obs_trace.events()}
        assert set(evs) == {"outer", "inner_a", "inner_b"}
        # children exit first (ring order), and each child's
        # [start, start+dur] interval nests inside the parent's
        names = [e[0] for e in obs_trace.events()]
        assert names == ["inner_a", "inner_b", "outer"]
        out_t0, out_dur = evs["outer"][2], evs["outer"][3]
        for child in ("inner_a", "inner_b"):
            t0, dur = evs[child][2], evs[child][3]
            assert out_t0 <= t0
            assert t0 + dur <= out_t0 + out_dur + 1e-9
        a, b = evs["inner_a"], evs["inner_b"]
        assert a[2] + a[3] <= b[2] + 1e-9  # a finished before b began

    def test_chrome_trace_schema(self, tracing, tmp_path):
        with obs_trace.span("round_dispatch", {"round": 0}):
            pass
        obs_trace.instant("lane_retire", {"n": 3})
        path = tmp_path / "trace.json"
        doc = obs_trace.export_chrome_trace(str(path))
        loaded = json.loads(path.read_text())
        assert loaded == doc
        assert isinstance(doc["traceEvents"], list)
        assert doc["displayTimeUnit"] in ("ms", "ns")
        phases = set()
        for ev in doc["traceEvents"]:
            # required keys of the trace-event format
            for key in ("name", "ph", "ts", "pid", "tid"):
                assert key in ev, f"missing {key} in {ev}"
            assert isinstance(ev["name"], str)
            assert ev["ph"] in ("X", "i", "B", "E", "M")
            assert isinstance(ev["ts"], (int, float))
            phases.add(ev["ph"])
            if ev["ph"] == "X":
                assert ev["dur"] >= 0
            if ev["ph"] == "i":
                assert ev.get("s") in ("t", "p", "g")
        assert phases == {"X", "i"}
        by_name = {e["name"]: e for e in doc["traceEvents"]}
        assert by_name["round_dispatch"]["args"] == {"round": 0}
        assert by_name["lane_retire"]["args"] == {"n": 3}

    def test_ring_bounding(self, tracing):
        obs_trace.set_ring_size(16)
        try:
            for i in range(100):
                with obs_trace.span("s"):
                    pass
            evs = obs_trace.events()
            assert len(evs) == 16
        finally:
            obs_trace.set_ring_size(65536)

    @pytest.mark.parametrize("record", sorted(RECORDERS))
    def test_disabled_records_nothing(self, record):
        obs_trace.set_enabled(False)
        obs_trace.clear()
        RECORDERS[record]("x", {"k": 1})
        assert obs_trace.events() == []

    @pytest.mark.parametrize("record", sorted(RECORDERS))
    def test_enabled_records_one_event(self, tracing, record):
        RECORDERS[record]("x", {"k": 1})
        (ev,) = obs_trace.events()
        assert ev[0] == "x" and ev[5] == {"k": 1}
        assert ev[1] == ("i" if record == "instant" else "X")

    def test_complete_keeps_the_interval_it_is_given(self, tracing):
        """An interval that is over goes into the ring as it was
        measured: the same tuple a span leaves, on the ring's clock."""
        with obs_trace.span("outer"):
            t0 = time.perf_counter()
            obs_trace.complete("stage", t0, 0.25, {"fun": "f"})
        stage, outer = obs_trace.events()
        assert stage == ("stage", "X", t0, 0.25, threading.get_ident(),
                         {"fun": "f"})
        assert outer[2] <= stage[2]
        doc = obs_trace.chrome_trace_events()
        assert doc[0]["dur"] == 0.25e6 and doc[0]["ph"] == "X"

    def test_disabled_span_is_shared_noop(self):
        """The off path hands back ONE module-level singleton — no
        object construction per call."""
        obs_trace.set_enabled(False)
        a = obs_trace.span("a")
        b = obs_trace.span("b", {"k": "v"})
        assert a is b is obs_trace._NOOP

    def test_disabled_hot_path_zero_allocation(self):
        """SKDIST_TRACE=0 contract: a tight span loop neither touches
        the ring (spy) nor grows the allocated-block count (alloc
        spy) — the instrumented round loop must cost nothing when
        tracing is off."""
        import sys

        obs_trace.set_enabled(False)
        appended = []
        real_ring = obs_trace._RING

        class _SpyRing:
            def append(self, ev):  # pragma: no cover - must not run
                appended.append(ev)

        obs_trace._RING = _SpyRing()
        try:
            def loop(n):
                for _ in range(n):
                    with obs_trace.span("hot"):
                        pass
                    obs_trace.instant("hot")
                    obs_trace.complete("hot", 0.0, 0.0)

            loop(64)  # warm up freelists/bytecode caches
            import gc

            gc.collect()
            before = sys.getallocatedblocks()
            loop(4096)
            gc.collect()
            delta = sys.getallocatedblocks() - before
        finally:
            obs_trace._RING = real_ring
        assert appended == []
        # allow a handful of blocks of interpreter noise, but nothing
        # scaling with the 4096 iterations (enabled tracing would
        # allocate >= 2 objects per iteration)
        assert delta < 64, f"disabled span loop allocated {delta} blocks"

    def test_set_enabled_env_reread(self, monkeypatch):
        monkeypatch.setenv("SKDIST_TRACE", "1")
        assert obs_trace.set_enabled(None) is True
        monkeypatch.setenv("SKDIST_TRACE", "0")
        assert obs_trace.set_enabled(None) is False


# ---------------------------------------------------------------------------
# views over the registry (faults / compile_cache / scoped attribution)
# ---------------------------------------------------------------------------

class TestRegistryViews:
    def test_faults_snapshot_is_registry_view(self):
        from skdist_tpu.parallel import faults

        faults.reset_stats()
        faults.record("rounds_retried", 2)
        snap = faults.snapshot()
        assert snap["rounds_retried"] == 2
        assert set(snap) == set(faults.FAULT_COUNTERS)
        assert obs_metrics.counter("faults.events").get(
            kind="rounds_retried"
        ) == 2
        faults.reset_stats()
        assert faults.snapshot()["rounds_retried"] == 0

    def test_faults_unknown_counter_raises(self):
        from skdist_tpu.parallel import faults

        with pytest.raises(KeyError):
            faults.record("not_a_counter")

    def test_compile_snapshot_is_registry_view(self):
        from skdist_tpu.parallel import compile_cache

        before = compile_cache.snapshot()
        compile_cache.kernel_memo(("obs-test", 1), lambda: object())
        after = compile_cache.snapshot()
        assert after["kernel_misses"] == before["kernel_misses"] + 1
        compile_cache.kernel_memo(("obs-test", 1), lambda: object())
        assert compile_cache.snapshot()["kernel_hits"] == \
            after["kernel_hits"] + 1

    def test_scoped_compile_attribution(self):
        from skdist_tpu.parallel import compile_cache

        base_a = compile_cache.scoped_misses("obs-eng-a")
        base_b = compile_cache.scoped_misses("obs-eng-b")
        with obs_metrics.compile_scope("obs-eng-a"):
            compile_cache.kernel_memo(("obs-scope", 1), lambda: object())
        # unscoped concurrent work moves the global counter only
        compile_cache.kernel_memo(("obs-scope", 2), lambda: object())
        assert compile_cache.scoped_misses("obs-eng-a") == base_a + 1
        assert compile_cache.scoped_misses("obs-eng-b") == base_b
        # hits never bill the scope
        with obs_metrics.compile_scope("obs-eng-a"):
            compile_cache.kernel_memo(("obs-scope", 1), lambda: object())
        assert compile_cache.scoped_misses("obs-eng-a") == base_a + 1

    def test_compile_scope_nests_and_restores(self):
        assert obs_metrics.current_scope() is None
        with obs_metrics.compile_scope("outer"):
            assert obs_metrics.current_scope() == "outer"
            with obs_metrics.compile_scope("inner"):
                assert obs_metrics.current_scope() == "inner"
            assert obs_metrics.current_scope() == "outer"
        assert obs_metrics.current_scope() is None


# ---------------------------------------------------------------------------
# RoundStats: the converged last_round_stats schema, pinned per path
# ---------------------------------------------------------------------------

#: the compacted loop's work and occupancy counts: present on every
#: path, None wherever the spec names no ``count_keys``
COUNT_KEYS = ("iters", "fevals", "lane_slots", "live_lane_slots")


def _assert_round_schema(stats, mode=None, counted=False):
    assert isinstance(stats, dict)
    missing = [k for k in ROUND_STATS_REQUIRED if k not in stats]
    assert not missing, f"missing RoundStats keys: {missing}"
    if mode is not None:
        assert stats["mode"] == mode
    for key in COUNT_KEYS:
        assert key in ROUND_STATS_REQUIRED
        assert (stats[key] is not None) == counted, (key, stats[key])


class TestRoundStatsSchema:
    def test_new_round_stats_prefills(self):
        st = new_round_stats("streamed", stream_mode="serial")
        _assert_round_schema(st, "streamed")
        assert st["kernel_mode"] is None
        assert st["retired_rung"] == 0
        assert st["stream_mode"] == "serial"

    def test_classic_local_path(self):
        from skdist_tpu.parallel import LocalBackend

        bk = LocalBackend()
        bk.batched_map(
            lambda sh, t: {"y": t["x"] * sh["s"]},
            {"x": np.arange(8, dtype=np.float32)},
            {"s": np.float32(2)}, round_size=4,
        )
        _assert_round_schema(bk.last_round_stats)
        assert bk.last_round_stats["mode"] in ("pipelined",
                                               "synchronous")
        assert bk.last_round_stats["tasks"] == 8
        assert bk.last_round_stats["rounds"] == 2

    def test_classic_mesh_path(self, tpu_backend):
        tpu_backend.batched_map(
            lambda sh, t: {"y": t["x"] + sh["s"]},
            {"x": np.arange(16, dtype=np.float32)},
            {"s": np.float32(1)},
        )
        _assert_round_schema(tpu_backend.last_round_stats)
        assert tpu_backend.last_round_stats["tasks"] == 16
        assert tpu_backend.last_round_stats["shared_bytes"] > 0

    def test_compacted_path(self):
        """A toy countdown carry drives the compacted slice loop."""
        from skdist_tpu.parallel import (
            IterativeKernelSpec,
            LocalBackend,
        )

        def init(shared, task):
            left = task["n"].astype(np.int32)
            return {"left": left, "done": left <= 0}

        def step(shared, task, carry):
            left = carry["left"] - 1
            return {"left": left, "done": left <= 0}

        def fin(shared, task, carry):
            return {"left": carry["left"]}

        spec = IterativeKernelSpec(
            init, step, fin, ("left",),
            fallback=lambda sh, t: {
                "left": np.zeros((), np.int32) * t["n"].astype(np.int32)
            },
        )
        bk = LocalBackend()
        tasks = {"n": np.arange(30, dtype=np.float32) % 4}
        out = bk.batched_map_iterative(spec, tasks, {}, round_size=8)
        assert (np.asarray(out["left"]) <= 0).all()
        st = bk.last_round_stats
        _assert_round_schema(st, "compacted")
        assert st["tasks"] == 30
        assert st["retired_convergence"] == 30
        assert st["retired_rung"] == 0

    def test_compacted_path_counted(self, tpu_backend):
        """The L-BFGS family names its counters: a search on the
        compacted path fills all four keys, one count per task."""
        from skdist_tpu.distribute.search import DistGridSearchCV
        from skdist_tpu.models import LogisticRegression

        rng = np.random.RandomState(0)
        X = rng.normal(size=(200, 6)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int64)
        DistGridSearchCV(
            LogisticRegression(max_iter=40, engine="xla"),
            {"C": [float(c) for c in np.logspace(-2, 2, 8)]},
            backend=tpu_backend, cv=4,
        ).fit(X, y)
        st = tpu_backend.last_round_stats
        _assert_round_schema(st, "compacted", counted=True)
        assert len(st["iters"]) == len(st["fevals"]) == st["tasks"] == 32
        assert all(f >= 2 * i + 1
                   for i, f in zip(st["iters"], st["fevals"]))
        assert max(st["iters"]) <= 40
        assert 0 < st["live_lane_slots"] <= st["lane_slots"]
        assert st["lane_slots"] == st["chunk"] * st["rounds"]

    def test_streamed_path(self):
        from skdist_tpu.data import ChunkedDataset
        from skdist_tpu.models import LogisticRegression
        from skdist_tpu.models.streaming import stream_fit_estimator
        from skdist_tpu.parallel import LocalBackend

        rng = np.random.RandomState(0)
        X = rng.normal(size=(256, 5)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int64)
        ds = ChunkedDataset.from_arrays(X, y, block_rows=64)
        bk = LocalBackend()
        stream_fit_estimator(
            LogisticRegression(max_iter=15, engine="xla"), ds,
            backend=bk,
        )
        st = bk.last_round_stats
        _assert_round_schema(st, "streamed")
        assert st["streamed_bytes"] > 0
        assert st["tasks"] == 1

    def test_publish_is_delta_idempotent(self):
        """Re-publishing a RoundStats after further accumulation folds
        only the delta (the streamed scoring pass extends the fit's
        dict; the compacted fallback publishes before downgrading) —
        and never double-counts the dispatch."""
        from skdist_tpu.obs.metrics import publish_round_stats

        st = new_round_stats("deltatest")
        st["streamed_bytes"] = 100
        sb = obs_metrics.counter("rounds.streamed_bytes")
        disp = obs_metrics.counter("rounds.dispatches")
        b0, d0 = sb.get(path="deltatest"), disp.get(path="deltatest")
        publish_round_stats(st)
        publish_round_stats(st)  # unchanged: no movement
        assert sb.get(path="deltatest") == b0 + 100
        st["streamed_bytes"] += 50
        publish_round_stats(st)
        assert sb.get(path="deltatest") == b0 + 150
        assert disp.get(path="deltatest") == d0 + 1

    def test_publish_folds_into_registry(self):
        from skdist_tpu.parallel import LocalBackend

        c = obs_metrics.counter("rounds.dispatches")
        before = c.get(path="pipelined")
        bk = LocalBackend()
        bk.batched_map(
            lambda sh, t: {"y": t["x"]},
            {"x": np.arange(4, dtype=np.float32)}, {},
        )
        assert c.get(path="pipelined") == before + 1
        rt = obs_metrics.counter("rounds.tasks")
        assert rt.get(path="pipelined") >= 4


# ---------------------------------------------------------------------------
# serving split + fleet labels
# ---------------------------------------------------------------------------

class TestServingStatsView:
    def test_by_model_split(self):
        from skdist_tpu.serve.stats import ServingStats

        st = ServingStats()
        st.record_submitted(serve_dtype="float32", model="m@1")
        st.record_completed(0.002, serve_dtype="float32", model="m@1")
        st.record_submitted(serve_dtype="int8", model="n@2")
        snap = st.snapshot()
        assert snap["by_model"]["m@1"]["requests"] == 1
        assert snap["by_model"]["m@1"]["completed"] == 1
        assert snap["by_model"]["m@1"]["p50_ms"] == pytest.approx(
            2.0, abs=0.5
        )
        assert snap["by_model"]["n@2"]["requests"] == 1
        assert snap["by_serve_dtype"]["int8"]["requests"] == 1

    def test_registry_leg_carries_labels(self):
        from skdist_tpu.serve.stats import ServingStats

        st = ServingStats()
        st.set_label(replica="3")
        st.record_submitted(model="m@1")
        got = obs_metrics.counter("serve.requests").get(
            engine=st.scope, replica="3", model="m@1"
        )
        assert got == 1

    def test_scoped_warm_mark_ignores_other_work(self):
        """A warm-marked engine's compiles_after_warmup stays 0 while
        OTHER scopes (another engine, unscoped background work)
        compile — the fleet-respawn false-trip regression."""
        from skdist_tpu.parallel import compile_cache
        from skdist_tpu.serve.stats import ServingStats

        st = ServingStats()
        with obs_metrics.compile_scope(st.scope):
            compile_cache.kernel_memo(("warmtest", st.scope),
                                      lambda: object())
        st.mark_warm()
        assert st.compiles_after_warmup() == 0
        # background / other-engine compiles do not move it
        compile_cache.kernel_memo(("warmtest", "bg"), lambda: object())
        other = ServingStats()
        with obs_metrics.compile_scope(other.scope):
            compile_cache.kernel_memo(("warmtest", other.scope),
                                      lambda: object())
        assert st.compiles_after_warmup() == 0
        # ... but this engine's own steady-state compile trips it
        with obs_metrics.compile_scope(st.scope):
            compile_cache.kernel_memo(("warmtest", st.scope, 2),
                                      lambda: object())
        assert st.compiles_after_warmup() == 1


# ---------------------------------------------------------------------------
# PR 15: distributed observability units (trace drops, context/stitch,
# state merge, exposition conformance, flight recorder, ops endpoint)
# ---------------------------------------------------------------------------

class TestTraceDrops:
    def test_overflow_bills_dropped_counter_and_export_metadata(
            self, tracing):
        """Satellite: trace-ring overflow is detectable — the
        ``trace.dropped_spans`` counter moves and the Chrome export's
        ``otherData.dropped`` marks the file truncated."""
        obs_trace.set_ring_size(8)
        try:
            before = obs_metrics.counter("trace.dropped_spans").get()
            for _ in range(20):
                with obs_trace.span("s"):
                    pass
            assert obs_trace.dropped() == 12
            after = obs_metrics.counter("trace.dropped_spans").get()
            assert after - before == 12
            doc = obs_trace.export_chrome_trace()
            assert doc["otherData"]["dropped"] == 12
            # a fresh ring exports clean again (counter stays cumulative)
            obs_trace.clear()
            with obs_trace.span("s"):
                pass
            assert obs_trace.export_chrome_trace()[
                "otherData"]["dropped"] == 0
        finally:
            obs_trace.set_ring_size(65536)


class TestTraceContext:
    def test_nested_spans_chain_parent_ids(self, tracing):
        ctx = obs_trace.new_context()
        with obs_trace.use_context(ctx):
            with obs_trace.span("route"):
                inner_ctx = obs_trace.current_context()
                with obs_trace.span("flush"):
                    pass
        evs = {e["name"]: e for e in obs_trace.chrome_trace_events()}
        route, flush = evs["route"], evs["flush"]
        assert route["args"]["trace_id"] == ctx["trace_id"]
        assert route["args"]["parent_id"] == ctx["span_id"]
        assert flush["args"]["parent_id"] == route["args"]["span_id"]
        assert inner_ctx["span_id"] == route["args"]["span_id"]
        # the thread context was restored on exit
        assert obs_trace.current_context() is None

    @pytest.mark.parametrize("record", sorted(RECORDERS))
    def test_no_context_spans_carry_no_ids(self, tracing, record):
        RECORDERS[record]("bare")
        ev = obs_trace.chrome_trace_events()[-1]
        assert "args" not in ev or "trace_id" not in ev.get("args", {})

    def test_complete_adopts_context(self, tracing):
        """An interval recorded when it is over parents under whatever
        span is open on its thread, with a span id of its own; the
        caller's args are not written into."""
        ctx = obs_trace.new_context()
        args = {"fun": "jit(f)"}
        with obs_trace.use_context(ctx):
            with obs_trace.span("compile"):
                obs_trace.complete("xla_compile", 1.0, 0.5, args)
                obs_trace.complete("xla_compile", 2.0, 0.5)
            obs_trace.complete("jax_trace", 3.0, 0.5)
        first, second, outer, loose = obs_trace.chrome_trace_events()
        assert first["args"]["trace_id"] == ctx["trace_id"]
        assert first["args"]["parent_id"] == outer["args"]["span_id"]
        assert second["args"]["parent_id"] == outer["args"]["span_id"]
        assert first["args"]["fun"] == "jit(f)" and args == {"fun": "jit(f)"}
        ids = {e["args"]["span_id"] for e in (first, second, outer, loose)}
        assert len(ids) == 4
        assert loose["args"]["parent_id"] == ctx["span_id"]
        # ... and leaves the thread's context as it found it
        assert obs_trace.current_context() is None

    def test_instant_adopts_context(self, tracing):
        ctx = obs_trace.new_context()
        with obs_trace.use_context(ctx):
            obs_trace.instant("elastic_epoch_agreement", {"epoch": 1})
        ev = obs_trace.chrome_trace_events()[-1]
        assert ev["args"]["trace_id"] == ctx["trace_id"]
        assert ev["args"]["parent_id"] == ctx["span_id"]

    def test_stitch_links_cross_process_spans(self, tracing):
        """A worker span whose parent_id lives in a DIFFERENT pid gets
        a flow-arrow pair; same-pid nesting does not."""
        ctx = obs_trace.new_context()
        with obs_trace.use_context(ctx):
            with obs_trace.span("route"):
                shipped = obs_trace.current_context()
        router = obs_trace.trace_part(label="router")
        # fake the worker's ring in "another process"
        obs_trace.clear()
        with obs_trace.use_context(shipped):
            with obs_trace.span("flush"):
                pass
        worker = obs_trace.trace_part(label="replica 0")
        worker["pid"] = router["pid"] + 1
        for ev in worker["events"]:
            ev["pid"] = worker["pid"]
        doc = obs_trace.stitch_traces([router, worker])
        names = {}
        for ev in doc["traceEvents"]:
            names.setdefault(ev["ph"], []).append(ev)
        # named process tracks for both parts
        meta = [e for e in names["M"] if e["name"] == "process_name"]
        assert {e["args"]["name"] for e in meta} == {"router",
                                                    "replica 0"}
        assert {e["pid"] for e in doc["traceEvents"]} >= {
            router["pid"], worker["pid"]}
        # exactly one flow pair: s at the router's route span, f at the
        # worker's flush span
        assert len(names.get("s", [])) == 1
        assert len(names.get("f", [])) == 1
        assert names["s"][0]["pid"] == router["pid"]
        assert names["f"][0]["pid"] == worker["pid"]
        assert names["s"][0]["id"] == names["f"][0]["id"]
        json.dumps(doc)  # the stitched doc is JSON-serializable


class TestStateMerge:
    def test_dump_merge_roundtrip_with_fleet_labels(self):
        src = MetricsRegistry()
        src.counter("serve.requests", help="req").inc(7, model="m@1")
        src.gauge("serve.queue_depth").set(3)
        src.histogram("serve.latency_s", buckets=(0.01, 0.1)).observe(
            0.05, model="m@1"
        )
        merged = MetricsRegistry()
        obs_metrics.merge_state(src.dump_state(), merged,
                                labels={"replica": 0, "pid": 41})
        assert merged.counter("serve.requests").get(
            model="m@1", replica="0", pid="41"
        ) == 7
        assert merged.gauge("serve.queue_depth").get(
            replica="0", pid="41"
        ) == 3
        count, total = merged.histogram("serve.latency_s").get(
            model="m@1", replica="0", pid="41"
        )
        assert count == 1 and total == pytest.approx(0.05)
        # histogram bucket layout traveled with the dump
        assert merged.histogram("serve.latency_s").buckets == (0.01, 0.1)

    def test_merge_accumulates_and_fleet_labels_win(self):
        """Two harvests of the same worker accumulate counters; a
        worker that self-labeled replica=9 is overridden by the
        supervisor's roster."""
        src = MetricsRegistry()
        src.counter("c").inc(2, replica="9")
        merged = MetricsRegistry()
        obs_metrics.merge_state(src.dump_state(), merged,
                                labels={"replica": 1})
        obs_metrics.merge_state(src.dump_state(), merged,
                                labels={"replica": 1})
        assert merged.counter("c").get(replica="1") == 4
        assert merged.counter("c").get(replica="9") == 0


def _parse_prometheus(text):
    """Tiny exposition parser for the round-trip pin: returns
    {(name, frozenset(label items)): float} and validates HELP/TYPE
    lines. Handles the three escaped characters in label values."""
    import re

    samples = {}
    types = {}
    helps = set()
    name_re = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")
    for line in text.strip().splitlines():
        if line.startswith("# HELP "):
            helps.add(line.split()[2])
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            assert name_re.match(name), name
            assert kind in ("counter", "gauge", "histogram")
            types[name] = kind
            continue
        assert not line.startswith("#"), f"unknown comment: {line!r}"
        if "{" in line:
            name, rest = line.split("{", 1)
            body, value = rest.rsplit("} ", 1)
            labels = {}
            lab_re = re.compile(
                r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"(?:,|$)'
            )
            pos = 0
            while pos < len(body):
                m = lab_re.match(body, pos)
                assert m, f"bad label body {body!r} at {pos}"
                raw = m.group(2)
                val = (raw.replace("\\\\", "\x00")
                       .replace('\\"', '"')
                       .replace("\\n", "\n")
                       .replace("\x00", "\\"))
                labels[m.group(1)] = val
                pos = m.end()
        else:
            name, value = line.rsplit(" ", 1)
            labels = {}
        assert name_re.match(name), name
        samples[(name, frozenset(labels.items()))] = float(value)
    return samples, types, helps


class TestExpositionConformance:
    def test_odd_label_values_roundtrip(self):
        r"""Satellite: a model named with backslashes, quotes, and
        newlines still emits exposition text a conforming parser reads
        back VERBATIM."""
        reg = MetricsRegistry()
        odd = 'we"ird\\mo,del\n@1'
        reg.counter("serve.requests", help="requests routed").inc(
            5, model=odd
        )
        reg.histogram("serve.latency_s", help="seconds",
                      buckets=(0.01,)).observe(0.5, model=odd)
        text = obs_export.prometheus_text(reg)
        samples, types, helps = _parse_prometheus(text)
        key = ("skdist_serve_requests_total",
               frozenset({("model", odd)}.union()))
        assert samples[key] == 5.0
        assert types["skdist_serve_requests_total"] == "counter"
        # histogram family got TYPE + HELP headers and parseable
        # bucket/sum/count samples carrying the odd label
        assert types["skdist_serve_latency_s"] == "histogram"
        assert "skdist_serve_latency_s" in helps
        assert samples[(
            "skdist_serve_latency_s_bucket",
            frozenset({("model", odd), ("le", "+Inf")}),
        )] == 1.0
        assert samples[(
            "skdist_serve_latency_s_count", frozenset({("model", odd)}),
        )] == 1.0

    def test_nonfinite_values_use_grammar_tokens(self):
        reg = MetricsRegistry()
        reg.gauge("g").set(float("inf"), k="a")
        reg.gauge("g").set(float("-inf"), k="b")
        text = obs_export.prometheus_text(reg)
        assert 'skdist_g{k="a"} +Inf' in text
        assert 'skdist_g{k="b"} -Inf' in text


class TestFlightRecorder:
    def test_ring_bounds_and_incident_dump(self, tmp_path):
        from skdist_tpu.obs.flightrec import FlightRecorder

        rec = FlightRecorder(capacity=8, min_interval_s=0.0)
        for i in range(20):
            rec.note("round", i=i)
        evs = rec.events()
        assert len(evs) == 8
        assert evs[-1]["i"] == 19
        path = rec.dump_incident(
            "unit/test reason", dir=str(tmp_path),
            extra={"replica": 1, "worker_flightrec": {"events": []}},
        )
        doc = json.loads(open(path).read())
        assert doc["schema"] == 1
        assert doc["kind"] == "incident"
        assert doc["reason"] == "unit/test reason"
        assert doc["pid"] == __import__("os").getpid()
        assert doc["extra"]["replica"] == 1
        assert [e["i"] for e in doc["events"]] == list(range(12, 20))
        assert "metrics" in doc and "spans" in doc
        # the reason was sanitized into the filename
        assert "unit_test" in path

    def test_incident_throttle(self, tmp_path):
        from skdist_tpu.obs.flightrec import FlightRecorder

        rec = FlightRecorder(min_interval_s=60.0)
        p1 = rec.dump_incident("r", dir=str(tmp_path))
        p2 = rec.dump_incident("r", dir=str(tmp_path))
        p3 = rec.dump_incident("other", dir=str(tmp_path))
        assert p1 is not None and p2 is None and p3 is not None

    def test_standing_autodump_atomic(self, tmp_path):
        import time as _time

        from skdist_tpu.obs.flightrec import FlightRecorder

        rec = FlightRecorder()
        rec.note("x", v=1)
        path = tmp_path / "standing.json"
        rec.start_autodump(str(path), interval_s=0.05)
        try:
            deadline = _time.monotonic() + 5.0
            while _time.monotonic() < deadline:
                if path.exists():
                    break
                _time.sleep(0.02)
            doc = json.loads(path.read_text())
            assert doc["kind"] == "snapshot"
            assert doc["events"][-1]["kind"] == "x"
        finally:
            rec.stop_autodump()
        # a later note lands in the final stop-time dump
        rec.note("y")
        rec.dump_now()
        doc = json.loads(path.read_text())
        assert doc["events"][-1]["kind"] == "y"

    def test_round_stats_feed(self):
        """publish_round_stats notes a round summary into the
        process recorder (the metrics→flightrec hook)."""
        from skdist_tpu.obs import flightrec

        stats = new_round_stats(mode="classic", rounds=3, tasks=24)
        obs_metrics.publish_round_stats(stats)
        kinds = [e for e in flightrec.recorder().events()
                 if e["kind"] == "round"]
        assert kinds and kinds[-1]["rounds"] == 3
        assert kinds[-1]["mode"] == "classic"

    def test_fault_record_feeds_recorder(self):
        from skdist_tpu.obs import flightrec
        from skdist_tpu.parallel import faults

        faults.record("rounds_retried")
        evs = [e for e in flightrec.recorder().events()
               if e["kind"] == "fault"]
        assert evs and evs[-1]["event"] == "rounds_retried"


class TestOpsEndpoint:
    def test_routes_and_status_codes(self):
        import urllib.error
        import urllib.request

        from skdist_tpu.obs import httpd as obs_httpd

        state = {"healthy": True}
        reg = MetricsRegistry()
        reg.counter("serve.requests").inc(3, replica="0")

        srv = obs_httpd.OpsServer(
            port=0,
            metrics=lambda: obs_export.prometheus_text(reg),
            healthz=lambda: dict(state),
        ).start()
        try:
            body = urllib.request.urlopen(
                srv.url + "/metrics", timeout=5
            ).read().decode()
            assert "skdist_serve_requests_total" in body
            assert 'replica="0"' in body
            with urllib.request.urlopen(
                    srv.url + "/healthz", timeout=5) as resp:
                assert resp.status == 200
                assert json.load(resp)["healthy"] is True
            state["healthy"] = False
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url + "/healthz", timeout=5)
            assert ei.value.code == 503
            doc = json.load(urllib.request.urlopen(
                srv.url + "/debug/flightrec", timeout=5
            ))
            assert doc["kind"] == "snapshot"
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url + "/nope", timeout=5)
            assert ei.value.code == 404
        finally:
            srv.stop()

    def test_off_by_default(self, monkeypatch):
        from skdist_tpu.obs import httpd as obs_httpd

        monkeypatch.delenv("SKDIST_OBS_PORT", raising=False)
        assert obs_httpd.start_from_env() is None
        assert obs_httpd.resolve_port(None) is None
        monkeypatch.setenv("SKDIST_OBS_PORT", "0")
        assert obs_httpd.resolve_port(None) == 0
