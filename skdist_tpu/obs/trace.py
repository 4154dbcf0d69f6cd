"""
Structured span tracing: one timeline for every plane of the framework.

Dapper-style nested spans (Sigelman et al., 2010) recorded into a
bounded in-process ring and exported as Chrome trace-event JSON —
loadable directly in Perfetto (``ui.perfetto.dev``) or
``chrome://tracing`` — so a whole search's dispatch structure
(``round_dispatch`` per device round, ``compile`` on every cache miss,
``block_feed`` per streamed block, ``flush`` per serving micro-batch,
``rung_eval`` per ASHA rung, ``replica_failover``/``replica_respawn``
on fleet events) reads as one picture instead of five subsystems' log
lines.

**Cost model.** Tracing is OFF by default and the off path is
allocation-free: ``span(name)`` returns a module-level no-op singleton
(no object construction, no ring append, no clock read) — the
``SKDIST_TRACE=0`` hot-path contract ``tests/test_obs.py`` pins with
an allocation spy. ``SKDIST_TRACE=1`` turns recording on; each span
costs two ``perf_counter`` reads and one ring append at exit
(:func:`complete`, for an interval that is already over, the append
alone).
Instrumentation sites are per-ROUND / per-BLOCK / per-FLUSH — never
per-task or per-row — so even traced overhead stays inside the
obs-smoke's 5% gate.

**Device-time attribution.** ``SKDIST_TRACE_JAX=1`` additionally
enters a ``jax.profiler.TraceAnnotation`` for every span, so a
chip-side profile capture (``jax.profiler.trace`` / XProf) attributes
device time to framework phases — the capture prerequisite of ROADMAP
item 5's chip legs. Off by default: the annotation has nonzero cost
even with no profiler session active.

**Bounding.** The ring holds the most recent ``SKDIST_TRACE_RING``
events (default 65536, ~15 MB of dicts at export time); older events
drop oldest-first, so a long-lived server can leave tracing on and
export a bounded tail on demand. Overflow is NOT silent: every evicted
event bills the ``trace.dropped_spans`` registry counter and the
export's ``otherData.dropped`` field, so a truncated trace is
detectable from both the exposition and the trace file itself.

**Cross-process context** (Dapper, Sigelman et al. 2010): a
(trace_id, span_id) pair rides :func:`new_context` /
:func:`use_context` / :func:`current_context`. While a context is
active, every recorded span allocates its own span id, re-points the
thread-local context for its duration (so nested spans — and spans on
threads that adopted the context — chain parent ids), and stamps
``trace_id``/``span_id``/``parent_id`` into its exported ``args``. A
request frame carries the context across a process boundary (the
procfleet wire protocol's ``_trace`` field); the worker adopts it, so
its ``flush``/``compile``/``bank_swap`` spans parent under the
router's span. :func:`stitch_traces` is the collector: it merges
per-process Chrome-trace rings (each exported on the WALL clock —
``clock="wall"`` — because each process's perf_counter epoch is
private) into one Perfetto-loadable file with named per-process
tracks and synthesized flow arrows (``ph: s/f``) from every
cross-process parent link.
"""

import json
import os
import threading
import time
import uuid
from collections import deque

__all__ = [
    "enabled",
    "set_enabled",
    "span",
    "complete",
    "instant",
    "events",
    "clear",
    "set_ring_size",
    "dropped",
    "new_context",
    "current_context",
    "use_context",
    "export_chrome_trace",
    "chrome_trace_events",
    "trace_part",
    "stitch_traces",
]


def _env_flag(name, default=False):
    raw = os.environ.get(name, "").strip().lower()
    if not raw:
        return default
    return raw in ("1", "true", "yes", "on")


#: module-level enabled flag — ONE attribute read on the hot path
_ENABLED = _env_flag("SKDIST_TRACE")
_JAX_ANNOTATE = _env_flag("SKDIST_TRACE_JAX")

_RING_SIZE = int(os.environ.get("SKDIST_TRACE_RING", "") or 65536)
#: (name, ph, t_start_s, dur_s, thread_id, args_or_None) tuples;
#: deque.append is atomic under the GIL — no lock on the record path
_RING = deque(maxlen=_RING_SIZE)

#: perf_counter epoch the exported timestamps are relative to, so a
#: trace's ts values start near 0 instead of at host-uptime microseconds
_EPOCH = time.perf_counter()
#: the SAME instant on the wall clock: perf_counter is process-private,
#: so cross-process stitching exports ts relative to this shared clock
#: (clock="wall") — within one process t_wall = t_perf - _EPOCH +
#: _EPOCH_WALL, and wall clocks agree across same-host processes
_EPOCH_WALL = time.time()

#: events evicted from the ring since it was last (re)created — the
#: export's truncation marker; the cumulative count also lands on the
#: ``trace.dropped_spans`` registry counter. Plain int updated under
#: the GIL next to the deque append (exactness under racing writers is
#: not worth a lock on the record path; the counter's job is "did the
#: ring overflow", not byte accounting).
_DROPPED = 0
_DROPPED_COUNTER = None


def _note_drop():
    global _DROPPED, _DROPPED_COUNTER
    _DROPPED += 1
    c = _DROPPED_COUNTER
    if c is None:
        from . import metrics as _metrics

        c = _DROPPED_COUNTER = _metrics.counter(
            "trace.dropped_spans",
            help="trace events evicted from the bounded ring",
        )
    c.inc()


def _append(ev):
    if len(_RING) == _RING_SIZE:
        _note_drop()
    _RING.append(ev)


def dropped():
    """Events evicted from the ring since it was last (re)created."""
    return _DROPPED


def enabled():
    """Whether span recording is on (cached; see :func:`set_enabled`)."""
    return _ENABLED


def set_enabled(flag=None):
    """Turn tracing on/off at runtime (tests, smokes, a server's admin
    endpoint). ``None`` re-reads ``SKDIST_TRACE`` from the environment.
    Returns the new state."""
    global _ENABLED
    _ENABLED = _env_flag("SKDIST_TRACE") if flag is None else bool(flag)
    return _ENABLED


def set_ring_size(n):
    """Re-bound the event ring (drops current contents and resets the
    export-side ``dropped`` marker; the registry counter stays
    cumulative)."""
    global _RING, _RING_SIZE, _DROPPED
    _RING_SIZE = max(1, int(n))
    _RING = deque(maxlen=_RING_SIZE)
    _DROPPED = 0


def clear():
    global _DROPPED
    _RING.clear()
    _DROPPED = 0


# ---------------------------------------------------------------------------
# trace/span context (cross-process parenting)
# ---------------------------------------------------------------------------

_CTX = threading.local()


def _span_id():
    return uuid.uuid4().hex[:16]


def new_context():
    """A fresh root context: ``{"trace_id", "span_id"}`` (hex ids).
    The creator's ``span_id`` is the parent of everything recorded
    under the context — a router makes one per request and ships it in
    the request frame."""
    return {"trace_id": uuid.uuid4().hex[:16], "span_id": _span_id()}


def current_context():
    """This thread's active context dict, or None."""
    return getattr(_CTX, "ctx", None)


class _CtxScope:
    __slots__ = ("ctx", "prev")

    def __init__(self, ctx):
        self.ctx = ctx
        self.prev = None

    def __enter__(self):
        self.prev = getattr(_CTX, "ctx", None)
        if self.ctx is not None:
            _CTX.ctx = dict(self.ctx)
        return self

    def __exit__(self, *exc):
        _CTX.ctx = self.prev
        return False


def use_context(ctx):
    """Context manager adopting ``ctx`` (a dict from
    :func:`new_context`, possibly shipped from another process) as this
    thread's active trace context. ``None`` is a no-op scope, so call
    sites need no branch."""
    return _CtxScope(ctx)


def _annotation(name):
    """A live jax.profiler.TraceAnnotation, or None when the passthrough
    is off or jax is unavailable."""
    if not _JAX_ANNOTATE:
        return None
    try:
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(name)
    except Exception:
        return None


class _Span:
    """One live span: records a complete ('X') event at exit. Nesting
    needs no explicit depth bookkeeping — Perfetto derives it from the
    containment of each thread's ts/dur intervals. When a trace
    context is active the span additionally allocates its own span id,
    chains the thread context under itself for its duration, and
    stamps the ids into its exported args (cross-process parenting —
    module docstring); with no context active none of that work
    happens."""

    __slots__ = ("name", "args", "t0", "_ann", "_ids", "_prev_ctx")

    def __init__(self, name, args):
        self.name = name
        self.args = args
        self.t0 = 0.0
        self._ann = None
        self._ids = None
        self._prev_ctx = None

    def __enter__(self):
        ann = _annotation(self.name)
        if ann is not None:
            ann.__enter__()
            self._ann = ann
        ctx = getattr(_CTX, "ctx", None)
        if ctx is not None:
            sid = _span_id()
            self._ids = {
                "trace_id": ctx["trace_id"],
                "span_id": sid,
                "parent_id": ctx["span_id"],
            }
            self._prev_ctx = ctx
            _CTX.ctx = {"trace_id": ctx["trace_id"], "span_id": sid}
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        args = self.args
        if self._ids is not None:
            _CTX.ctx = self._prev_ctx
            args = dict(args) if args else {}
            args.update(self._ids)
        _append((
            self.name, "X", self.t0, t1 - self.t0,
            threading.get_ident(), args,
        ))
        return False


class _NoopSpan:
    """The disabled-path singleton: enter/exit do nothing and allocate
    nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


def span(name, args=None):
    """Context manager recording one nested span while tracing is on.

    ``args`` (an optional dict) lands in the exported event's ``args``
    — build it only when :func:`enabled` is true, or the allocation
    defeats the off-path zero-cost contract (which is also why this is
    a positional dict rather than ``**kwargs``: an empty kwargs dict
    would be allocated per call even when disabled)."""
    if not _ENABLED:
        return _NOOP
    return _Span(name, args)


def complete(name, t0, dur, args=None):
    """Record an interval that has ALREADY ended as one complete ('X')
    event: ``t0`` on the ring's clock (``time.perf_counter``), ``dur``
    in seconds. For work that reports itself when it is over (JAX
    announces a trace, a lowering or a backend compile at its end:
    ``parallel/compile_cache``'s listeners), where no context manager
    can be entered. While a trace context is active the event gets a
    span id of its own under the context's — it parents under whatever
    span is open on the recording thread — as :class:`_Span` stamps
    them. Such a span is over, so it is never a ``TraceAnnotation``;
    the spans that enclose it are."""
    if not _ENABLED:
        return
    ctx = getattr(_CTX, "ctx", None)
    if ctx is not None:
        args = dict(args) if args else {}
        args.update(trace_id=ctx["trace_id"], span_id=_span_id(),
                    parent_id=ctx["span_id"])
    _append((name, "X", t0, dur, threading.get_ident(), args))


def instant(name, args=None):
    """Record a zero-duration instant event ('i' phase — rendered as a
    flag line in Perfetto): rung kills, lane retirements, elastic
    shrinks, replica failovers."""
    if not _ENABLED:
        return
    ctx = getattr(_CTX, "ctx", None)
    if ctx is not None:
        args = dict(args) if args else {}
        args.setdefault("trace_id", ctx["trace_id"])
        args.setdefault("parent_id", ctx["span_id"])
    _append((
        name, "i", time.perf_counter(), 0.0,
        threading.get_ident(), args,
    ))


def events():
    """The ring's current events as raw tuples (oldest first)."""
    return list(_RING)


def chrome_trace_events(clock="epoch", limit=None):
    """The ring rendered as Chrome trace-event dicts (the
    ``traceEvents`` array): complete events carry ``ph="X"`` with
    microsecond ``ts``/``dur``; instants carry ``ph="i"`` with thread
    scope. ``clock="epoch"`` (default) makes timestamps relative to the
    module's import epoch (single-process traces start near 0);
    ``clock="wall"`` rebases them onto the wall clock so rings from
    different processes of one host line up for :func:`stitch_traces`.
    ``limit`` renders only the ring's most recent N events — callers
    on a CADENCE (the flight recorder's per-second standing dump, the
    fleet's telemetry harvest) must bound this, or a full 64k ring
    costs ~15 MB of dicts per tick.
    """
    base = _EPOCH if clock == "epoch" else (_EPOCH - _EPOCH_WALL)
    pid = os.getpid()
    out = []
    ring = list(_RING)
    if limit is not None:
        ring = ring[-int(limit):]
    for name, ph, t0, dur, tid, args in ring:
        ev = {
            "name": name,
            "cat": "skdist",
            "ph": ph,
            "ts": (t0 - base) * 1e6,
            "pid": pid,
            "tid": tid,
        }
        if ph == "X":
            ev["dur"] = dur * 1e6
        else:
            ev["s"] = "t"
        if args:
            ev["args"] = dict(args)
        out.append(ev)
    return out


def export_chrome_trace(path=None, clock="epoch"):
    """Export the ring as a Chrome trace-event JSON object (and write
    it to ``path`` when given). The object form (``{"traceEvents":
    [...], "displayTimeUnit": "ms"}``) is what Perfetto's legacy JSON
    importer and ``chrome://tracing`` both load. ``otherData.dropped``
    counts events the bounded ring evicted — nonzero means the file is
    a truncated tail, not the whole story."""
    doc = {
        "traceEvents": chrome_trace_events(clock=clock),
        "displayTimeUnit": "ms",
        "otherData": {
            "producer": "skdist_tpu.obs.trace",
            "dropped": int(_DROPPED),
        },
    }
    if path is not None:
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(doc, fh)
    return doc


# ---------------------------------------------------------------------------
# the collector: stitch per-process rings into one trace
# ---------------------------------------------------------------------------

def trace_part(label=None, limit=None):
    """This process's ring as a stitchable part (wall-clock events +
    identity + truncation marker) — what the procfleet ``telemetry``
    harvest ships from each worker. ``limit`` bounds the shipped tail
    (the harvest runs on an interval; an unbounded full ring would
    cost ~15 MB of pickle per replica per tick with tracing on)."""
    n = len(_RING)
    shipped = n if limit is None else min(n, int(limit))
    return {
        "pid": os.getpid(),
        "label": label or f"pid {os.getpid()}",
        "dropped": int(_DROPPED) + (n - shipped),
        "events": chrome_trace_events(clock="wall", limit=limit),
    }


def stitch_traces(parts, path=None):
    """Merge per-process trace parts (:func:`trace_part` dicts) into
    ONE Perfetto-loadable Chrome trace document.

    - every part's events keep their own ``pid`` (overridden by the
      part's ``pid`` when the events lack one), so each process is its
      own track group, and a ``process_name`` metadata event names the
      track with the part's ``label`` (e.g. ``replica 1 (pid 4242)``);
    - parent links that cross a process boundary (a span whose
      ``args.parent_id`` was recorded in a DIFFERENT pid — the shipped
      request context) become Chrome flow arrows: an ``s`` event at
      the parent span and a matching ``f`` (``bp: "e"``) at the child,
      so Perfetto draws the router→worker causality;
    - ``otherData.dropped`` sums every part's eviction count.

    Events must have been exported on the wall clock
    (``chrome_trace_events(clock="wall")``); same-host processes share
    it, which is the procfleet deployment shape."""
    parts = list(parts)
    events = []
    dropped_total = 0
    span_home = {}  # span_id -> (pid, tid, ts) of the span that owns it
    for part in parts:
        pid = int(part.get("pid") or 0)
        dropped_total += int(part.get("dropped") or 0)
        events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "ts": 0, "args": {"name": str(part.get("label") or pid)},
        })
        for ev in part.get("events", ()):
            ev = dict(ev)
            ev.setdefault("pid", pid)
            events.append(ev)
            args = ev.get("args") or {}
            sid = args.get("span_id")
            if sid:
                span_home[sid] = (ev["pid"], ev.get("tid", 0), ev["ts"])
    flows = []
    for ev in events:
        args = ev.get("args") or {}
        parent = args.get("parent_id")
        if not parent or parent not in span_home:
            continue
        ppid, ptid, pts = span_home[parent]
        if ppid == ev.get("pid"):
            continue  # same-process nesting: containment already shows it
        fid = args.get("span_id") or f"i{len(flows)}"
        flows.append({
            "name": "route", "cat": "skdist.flow", "ph": "s",
            "id": fid, "pid": ppid, "tid": ptid, "ts": pts,
        })
        flows.append({
            "name": "route", "cat": "skdist.flow", "ph": "f", "bp": "e",
            "id": fid, "pid": ev["pid"], "tid": ev.get("tid", 0),
            "ts": ev["ts"],
        })
    doc = {
        "traceEvents": events + flows,
        "displayTimeUnit": "ms",
        "otherData": {
            "producer": "skdist_tpu.obs.trace.stitch",
            "dropped": dropped_total,
            "processes": len(parts),
        },
    }
    if path is not None:
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(doc, fh)
    return doc
