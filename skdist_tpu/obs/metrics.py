"""
Process-wide metrics registry: the one place every subsystem's signals
land.

Before this module the framework's observability was a pile of ad-hoc
dicts — three independent ``last_round_stats`` construction sites in
``parallel/backend.py`` (classic, iterative, streamed, each with its
own key set), a serving-only ``ServingStats``, and standalone counter
dicts in ``faults.py`` and ``compile_cache.py``. The registry replaces
all of them as the *store*; the old surfaces (``faults.snapshot()``,
``compile_cache.snapshot()``, ``backend.last_round_stats``,
``serve.stats()``) remain as *views* over it, and the exporters
(``obs.export``: Prometheus text exposition, JSON snapshot) and the
span tracer (``obs.trace``) read from the same place — Prometheus'
"one registry, many collectors, label dimensions for the rest" model
(Prometheus client_golang; Borgmon before it).

Three metric kinds, all thread-safe and labelable:

- :class:`Counter` — monotonically increasing value (int or float —
  ``lower_time_s`` style wall accumulators are float counters).
- :class:`Gauge` — set-to-current value (queue depth, mesh extent).
- :class:`Histogram` — fixed bucket counts (Prometheus ``le``
  semantics: cumulative at exposition time) PLUS a bounded sample ring
  for exact rolling percentiles (the serving-latency p50/p99 view —
  bucket interpolation would be too coarse for sub-ms SLOs).

Labels are passed as keyword arguments to the record calls
(``counter("serve.requests", model="m@1").inc()``); a metric family is
one name, its children one value per label tuple. The empty label set
is a legitimate child ("the unlabeled total").

**Scoped compile attribution** (:func:`compile_scope`): a thread-local
tag the compile cache stamps onto its miss counters, so a serving
engine can count the compiles *its* dispatches caused — process-global
counters alone cannot distinguish a served shape escaping the bucket
set from a background fit compiling in the same process (the
``compiles_after_warmup`` false-trip ``serve/stats.py``'s old module
docstring admitted to).

**RoundStats** (:func:`new_round_stats`): the converged per-dispatch
schema of ``backend.last_round_stats``. Every dispatch path (classic,
compacted/iterative, streamed, streamed-predict) starts from the same
required key set — missing values are explicitly ``None``/0, never
absent — and :func:`publish_round_stats` folds the dispatch's totals
into the registry when it completes, so the per-call dict is the
recent-history view and the registry the cumulative one.
"""

import math
import threading
from collections import deque

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "registry",
    "counter",
    "gauge",
    "histogram",
    "compile_scope",
    "current_scope",
    "ROUND_STATS_REQUIRED",
    "RoundStats",
    "new_round_stats",
    "publish_round_stats",
    "merge_state",
]

_DEFAULT_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0,
)


def _label_key(labels):
    """Canonical hashable form of a label dict: sorted (k, v) tuple,
    values coerced to str (Prometheus labels are strings)."""
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class _Metric:
    """One family: a name plus per-label-tuple children.

    Each family carries its OWN lock: the record paths (serving
    submit/complete, per-round billing) run on many threads at once,
    and a single registry-wide lock measurably serialised the serving
    hot path under concurrent clients. The registry's lock guards only
    family creation."""

    kind = "untyped"

    def __init__(self, name, registry, help=""):
        self.name = name
        self.help = help
        self._registry = registry
        self._lock = threading.Lock()

    def labels(self, **labels):
        raise NotImplementedError


class _BoundCounter:
    """Pre-resolved handle to ONE label child: the hot-path form —
    the label dict build + sort happened once at :meth:`Counter.child`
    time, so ``inc`` is a lock + a dict update. Serving's per-request
    record calls go through these."""

    __slots__ = ("_fam", "_key")

    def __init__(self, fam, key):
        self._fam = fam
        self._key = key

    def inc(self, n=1):
        fam = self._fam
        with fam._lock:
            fam._values[self._key] = fam._values.get(self._key, 0) + n


class Counter(_Metric):
    """Monotonic counter family (int or float increments)."""

    kind = "counter"

    def __init__(self, name, registry, help=""):
        super().__init__(name, registry, help)
        self._values = {}

    def inc(self, n=1, **labels):
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0) + n

    def child(self, **labels):
        """Bound handle for repeated increments of one label child."""
        return _BoundCounter(self, _label_key(labels))

    def get(self, **labels):
        with self._lock:
            return self._values.get(_label_key(labels), 0)

    def total(self):
        """Sum over every label child."""
        with self._lock:
            return sum(self._values.values()) if self._values else 0

    def children(self):
        with self._lock:
            return dict(self._values)

    def reset(self):
        with self._lock:
            self._values.clear()


class Gauge(_Metric):
    """Set-to-current value family."""

    kind = "gauge"

    def __init__(self, name, registry, help=""):
        super().__init__(name, registry, help)
        self._values = {}

    def set(self, value, **labels):
        with self._lock:
            self._values[_label_key(labels)] = value

    def inc(self, n=1, **labels):
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0) + n

    def get(self, **labels):
        with self._lock:
            return self._values.get(_label_key(labels), 0)

    def child(self, **labels):
        """Bound handle (``set``/``inc``) for one label child."""
        return _BoundGauge(self, _label_key(labels))

    def children(self):
        with self._lock:
            return dict(self._values)

    def reset(self):
        with self._lock:
            self._values.clear()


class _BoundGauge:
    """Pre-resolved handle to one gauge child (see _BoundCounter)."""

    __slots__ = ("_fam", "_key")

    def __init__(self, fam, key):
        self._fam = fam
        self._key = key

    def set(self, value):
        fam = self._fam
        with fam._lock:
            fam._values[self._key] = value

    def inc(self, n=1):
        fam = self._fam
        with fam._lock:
            fam._values[self._key] = fam._values.get(self._key, 0) + n


class _HistChild:
    __slots__ = ("counts", "sum", "count", "ring")

    def __init__(self, n_buckets, window):
        self.counts = [0] * (n_buckets + 1)  # + overflow (+Inf)
        self.sum = 0.0
        self.count = 0
        self.ring = deque(maxlen=window)


class Histogram(_Metric):
    """Fixed-boundary histogram + bounded percentile ring.

    ``observe`` bills the matching bucket (upper-bound semantics: the
    first boundary >= the value, like Prometheus ``le``) and appends
    the raw sample to a bounded ring; :meth:`percentile` computes the
    exact linear-interpolated percentile of the ring's window —
    matching ``numpy.percentile``'s default method on the same samples
    — so rolling latency views stay exact while the Prometheus
    exposition stays fixed-cost.
    """

    kind = "histogram"

    def __init__(self, name, registry, help="", buckets=_DEFAULT_BUCKETS,
                 window=4096):
        super().__init__(name, registry, help)
        self.buckets = tuple(sorted(float(b) for b in buckets))
        self.window = int(window)
        self._children = {}

    def _child(self, key):
        child = self._children.get(key)
        if child is None:
            child = self._children[key] = _HistChild(
                len(self.buckets), self.window
            )
        return child

    def observe(self, value, **labels):
        self._observe(_label_key(labels), float(value))

    def _observe(self, key, value):
        with self._lock:
            child = self._child(key)
            i = 0
            for b in self.buckets:
                if value <= b:
                    break
                i += 1
            child.counts[i] += 1
            child.sum += value
            child.count += 1
            child.ring.append(value)

    def child(self, **labels):
        """Bound handle (``observe``) for one label child."""
        return _BoundHistogram(self, _label_key(labels))

    def percentile(self, q, **labels):
        """Exact percentile of the rolling sample window (``q`` in
        [0, 100], numpy 'linear' interpolation), or None when empty."""
        with self._lock:
            child = self._children.get(_label_key(labels))
            samples = list(child.ring) if child is not None else []
        if not samples:
            return None
        samples.sort()
        if len(samples) == 1:
            return samples[0]
        rank = (float(q) / 100.0) * (len(samples) - 1)
        lo = int(math.floor(rank))
        hi = min(lo + 1, len(samples) - 1)
        frac = rank - lo
        return samples[lo] * (1.0 - frac) + samples[hi] * frac

    def get(self, **labels):
        """(count, sum) of one child."""
        with self._lock:
            child = self._children.get(_label_key(labels))
            if child is None:
                return 0, 0.0
            return child.count, child.sum

    def children(self):
        """{label key: {"counts", "sum", "count"}} — counts are
        PER-BUCKET (non-cumulative); the exporter cumulates for ``le``."""
        with self._lock:
            return {
                key: {
                    "counts": list(c.counts),
                    "sum": c.sum,
                    "count": c.count,
                }
                for key, c in self._children.items()
            }

    def merge_child(self, key, dump):
        """Fold one harvested child dump (a :meth:`children` value)
        into the child at label ``key`` — the fleet-merge path. Bucket
        layouts must match (the harvest recreates the family with the
        dumped boundaries); the percentile ring does NOT travel (raw
        samples stay process-local — the merged view keeps bucket
        counts/sum/count, which is what the exposition serves)."""
        counts = dump["counts"]
        if len(counts) != len(self.buckets) + 1:
            raise ValueError(
                f"histogram {self.name!r}: merge of {len(counts)} "
                f"buckets into {len(self.buckets) + 1}"
            )
        with self._lock:
            child = self._child(key)
            for i, c in enumerate(counts):
                child.counts[i] += int(c)
            child.sum += float(dump["sum"])
            child.count += int(dump["count"])

    def reset(self):
        with self._lock:
            self._children.clear()


class _BoundHistogram:
    """Pre-resolved handle to one histogram child (see _BoundCounter)."""

    __slots__ = ("_fam", "_key")

    def __init__(self, fam, key):
        self._fam = fam
        self._key = key

    def observe(self, value):
        self._fam._observe(self._key, float(value))


class MetricsRegistry:
    """Thread-safe name → metric-family store (module docstring).

    One process-wide default instance backs the whole framework
    (:func:`registry`); tests may build private instances. Family kinds
    are sticky: asking for an existing name with a different kind
    raises (a silent kind change would corrupt the exposition).
    """

    def __init__(self):
        self._lock = threading.RLock()
        self._metrics = {}

    def _family(self, cls, name, **kwargs):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, self, **kwargs)
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} is a {m.kind}, not a {cls.kind}"
                )
            elif not m.help and kwargs.get("help"):
                # help is sticky at the first NON-EMPTY registration: a
                # bare counter(name) peek (tests, ad-hoc reads) must not
                # strip the HELP line off the family's real
                # registration site for the rest of the process
                m.help = kwargs["help"]
            return m

    def counter(self, name, help=""):
        return self._family(Counter, name, help=help)

    def gauge(self, name, help=""):
        return self._family(Gauge, name, help=help)

    def histogram(self, name, help="", buckets=_DEFAULT_BUCKETS,
                  window=4096):
        return self._family(Histogram, name, help=help, buckets=buckets,
                            window=window)

    def families(self):
        with self._lock:
            return dict(self._metrics)

    def get(self, name):
        with self._lock:
            return self._metrics.get(name)

    def reset(self, prefix=None):
        """Zero every family (or only those whose name starts with
        ``prefix``). Family objects are kept — handles stay live."""
        with self._lock:
            for name, m in self._metrics.items():
                if prefix is None or name.startswith(prefix):
                    m.reset()

    def snapshot(self):
        """Plain-dict dump: {name: {"kind", "values": {label key:
        value-or-hist dict}}} — the JSON exporter's input."""
        return snapshot_families(self.families())

    def dump_state(self):
        """The registry's full state in a merge-round-trippable form:
        ``{name: {"kind", "help", "children": {label-key tuple:
        value}}}`` (histograms add ``buckets``/``window``). Unlike
        :meth:`snapshot` the label keys stay STRUCTURED tuples — this
        is the telemetry-harvest wire form (it rides the procfleet's
        pickle frames), and :func:`merge_state` rebuilds exact label
        children from it, with fleet labels layered on top."""
        out = {}
        for name, m in self.families().items():
            ent = {"kind": m.kind, "help": m.help,
                   "children": m.children()}
            if m.kind == "histogram":
                ent["buckets"] = tuple(m.buckets)
                ent["window"] = m.window
            out[name] = ent
        return out


def snapshot_families(families):
    """Render a {name: family} mapping as nested plain dicts — the ONE
    definition of the snapshot's label-key format, shared by
    :meth:`MetricsRegistry.snapshot` and the exporters' family-subset
    views (``obs.export.fleet_snapshot``)."""
    out = {}
    for name, m in sorted(families.items()):
        out[name] = {"kind": m.kind, "values": {
            "|".join(f"{k}={v}" for k, v in key) if key else "": val
            for key, val in m.children().items()
        }}
    return out


def merge_state(state, into, labels=None):
    """Fold one process's :meth:`MetricsRegistry.dump_state` into the
    ``into`` registry, layering ``labels`` (e.g. ``{"replica": "1",
    "pid": "4242"}``) onto every child — the fleet-merge primitive the
    procfleet supervisor uses to build one exposition covering every
    worker. Fleet labels WIN over same-named labels the worker already
    carried (the supervisor's roster is the authority on which replica
    slot a process occupies). Counters/histograms accumulate, gauges
    last-write-win per label child."""
    labels = {str(k): str(v) for k, v in (labels or {}).items()}
    for name, ent in state.items():
        kind = ent.get("kind")
        for key, val in ent.get("children", {}).items():
            child_labels = dict(key)
            child_labels.update(labels)
            if kind == "counter":
                into.counter(name, help=ent.get("help", "")).inc(
                    val, **child_labels
                )
            elif kind == "gauge":
                into.gauge(name, help=ent.get("help", "")).set(
                    val, **child_labels
                )
            elif kind == "histogram":
                fam = into.histogram(
                    name, help=ent.get("help", ""),
                    buckets=ent.get("buckets", _DEFAULT_BUCKETS),
                    window=ent.get("window", 4096),
                )
                fam.merge_child(_label_key(child_labels), val)
    return into


_REGISTRY = MetricsRegistry()


def registry():
    """The process-wide default registry."""
    return _REGISTRY


def counter(name, help=""):
    return _REGISTRY.counter(name, help=help)


def gauge(name, help=""):
    return _REGISTRY.gauge(name, help=help)


def histogram(name, help="", buckets=_DEFAULT_BUCKETS, window=4096):
    return _REGISTRY.histogram(name, help=help, buckets=buckets,
                               window=window)


# ---------------------------------------------------------------------------
# scoped compile attribution
# ---------------------------------------------------------------------------

_SCOPE = threading.local()


class _ScopeCtx:
    __slots__ = ("tag", "prev")

    def __init__(self, tag):
        self.tag = tag
        self.prev = None

    def __enter__(self):
        self.prev = getattr(_SCOPE, "tag", None)
        _SCOPE.tag = self.tag
        return self

    def __exit__(self, *exc):
        _SCOPE.tag = self.prev
        return False


def compile_scope(tag):
    """Context manager tagging this thread's compile misses with
    ``tag`` (see module docstring): while active,
    ``compile_cache``'s miss counters additionally bill
    ``compile.scoped_misses{scope=tag}``, which is what a serving
    engine's ``compiles_after_warmup`` measures — per-engine deltas
    that concurrent non-serving work cannot move."""
    return _ScopeCtx(str(tag))


def current_scope():
    """This thread's active compile-attribution tag, or None."""
    return getattr(_SCOPE, "tag", None)


# ---------------------------------------------------------------------------
# RoundStats: the converged last_round_stats schema
# ---------------------------------------------------------------------------

#: keys EVERY dispatch path's ``last_round_stats`` carries, with their
#: explicit "nothing happened" values — the schema contract the
#: regression tests pin per path. ``mode`` is the path discriminator
#: ("pipelined"/"synchronous" classic, "compacted", "streamed",
#: "streamed_predict"); ``kernel_mode`` is stamped by the estimator
#: dispatch sites (``models/linear.annotate_round_kernel_mode``) and
#: stays None for non-estimator dispatches; the retirement split is 0
#: outside the compacted path; the byte accounting is 0 where no bytes
#: moved on that leg.
ROUND_STATS_REQUIRED = {
    "mode": None,            # path discriminator
    "rounds": 0,             # device rounds (or blocks grouped) run
    "tasks": 0,              # tasks the dispatch covered
    "kernel_mode": None,     # dense / packed_* / hist_tree / None
    "retries": 0,            # fault re-dispatches
    "refused": 0,            # rounds / probe compiles refused for memory
    "dispatch_s": 0.0,       # host time slicing/placing/enqueueing
    "gather_wait_s": 0.0,    # host time blocked on device results
    "retired_rung": 0,       # lanes killed by an adaptive rung
    "retired_convergence": 0,  # lanes that ran to convergence/cap
    "shared_bytes": 0,       # placed shared-tree bytes (broadcast leg)
    "streamed_bytes": 0,     # H2D-fed block bytes (streaming leg)
    # streamed-rung accounting (both are documented upper-bound
    # estimates — see models/streaming's rung seams): solver passes the
    # killed lanes would still have paid, and whole-dataset bytes the
    # shortened race never streamed
    "passes_saved": 0,
    "streamed_bytes_saved": 0,
    # binned-block-cache accounting (streamed GBDT): bytes written
    # building the uint8 cache this fit (0 on a cache HIT — the 4x
    # read-amplification win is observable, not asserted) and bytes
    # read back from it across all boosting passes
    "binned_bytes_cached": 0,
    "binned_bytes_streamed": 0,
    "rung_survivors": None,  # per-rung survivor counts, "12,4,2"
    # work and occupancy counts of the compacted loop, None on every
    # path (and for every spec) that has no ``count_keys``: per task
    # the solver's iterations and the loss evaluations it asked for,
    # and the lane slots dispatched against those that carried a real,
    # still-running fit when their round was enqueued
    "iters": None,
    "fevals": None,
    "lane_slots": None,
    "live_lane_slots": None,
    # how the compacted path sized its rounds, None on every other
    # path: the rule that set ``chunk`` ("round_size", "all_tasks",
    # "amortised", "memory" or "target_rounds":
    # ``backend.iterative_chunk_size``) and the cap device memory put
    # on a round's lanes (None where the device reports no memory)
    "chunk_basis": None,
    "lanes_fit": None,
}


class RoundStats(dict):
    """``last_round_stats`` with the required schema pre-filled: a
    plain dict to every existing consumer, plus the guarantee that the
    :data:`ROUND_STATS_REQUIRED` keys exist from construction.
    ``_published`` (attribute, not a key — it must never appear in the
    user-visible dict) records the values already folded into the
    registry, so re-publishing after further accumulation (a streamed
    fit's scoring pass mutating the same dict) folds only the delta."""

    __slots__ = ("_published",)


def new_round_stats(mode=None, **extra):
    """One dispatch's stats dict with the converged schema pre-filled
    (required keys present with explicit None/0 defaults, ``mode`` and
    any path-specific ``extra`` applied on top)."""
    stats = RoundStats(ROUND_STATS_REQUIRED)
    stats["mode"] = mode
    stats.update(extra)
    return stats


#: RoundStats keys folded into registry counters at publish (numeric
#: accumulators only — the discriminators/mode strings stay view-side)
_ROUND_PUBLISH_KEYS = (
    "rounds", "tasks", "retries", "dispatch_s", "gather_wait_s",
    "retired_rung", "retired_convergence", "streamed_bytes",
    "passes_saved", "streamed_bytes_saved",
    "binned_bytes_cached", "binned_bytes_streamed",
)


def publish_round_stats(stats):
    """Fold one completed dispatch's RoundStats into the registry:
    ``rounds.<key>`` counters labeled by dispatch path, plus a
    ``rounds.dispatches`` counter — the cumulative half of the
    "registry backs last_round_stats" contract. Tolerant of partial
    dicts (a caller that died mid-dispatch publishes what it has), and
    IDEMPOTENT-BY-DELTA on :class:`RoundStats`: a second publish after
    further accumulation (the streamed scoring pass extends the fit's
    dict; a compacted attempt publishes before downgrading to the
    classic fallback) folds only what moved since the first."""
    if not isinstance(stats, dict):
        return
    path = str(stats.get("mode"))
    prev = getattr(stats, "_published", None)
    if prev is None:
        counter("rounds.dispatches").inc(1, path=path)
        prev = {}
    for key in _ROUND_PUBLISH_KEYS:
        delta = (stats.get(key) or 0) - prev.get(key, 0)
        if delta > 0:  # counters stay monotonic even if a view resets
            counter(f"rounds.{key}").inc(delta, path=path)
    try:
        stats._published = {
            k: (stats.get(k) or 0) for k in _ROUND_PUBLISH_KEYS
        }
    except AttributeError:  # plain dict: single-shot publish only
        pass
    # kernel_mode is stamped AFTER the dispatch returns
    # (models/linear.annotate_round_kernel_mode), which bills the
    # rounds.kernel_mode counter itself — not double-counted here
    from . import flightrec

    flightrec.recorder().note_round(stats)
