"""
Fault tolerance for the fan-out data plane.

The reference sk-dist inherited fault tolerance from Spark: a failed
inner fit was re-executed on another executor by lineage (RDD,
NSDI'12), so a transient device error cost one task, not the search.
The fan-out backend has no scheduler underneath it — this module is
that layer, in four parts shared by the round loop, the CV search, and
the serving engine:

1. **Taxonomy + retry** (:func:`classify`, :class:`RetryPolicy`): a
   typed classification of what a failed round means — transient XLA
   runtime errors and preemptions are retryable at round granularity
   (the round's inputs are immutable host slices, so a re-dispatch is
   bitwise identical); RESOURCE_EXHAUSTED keeps its dedicated
   shrink-and-resume machinery; everything else stays fail-loud.
   ``SKDIST_ROUND_RETRIES`` / ``SKDIST_RETRY_BACKOFF_MS`` are the
   knobs.

2. **Lane quarantine** (:func:`nonfinite_lanes`): a non-finite guard
   over batched outputs. A numerically diverging task poisons only its
   own lane of the vmapped program; the guard maps poisoned lanes to
   sklearn ``error_score`` semantics (search) or a
   ``FitFailedWarning`` (OvR/OvO) instead of letting NaN rank.
   ``SKDIST_FAULT_GUARD=0`` is the kill switch.

3. **Durable search checkpoints** (:class:`SearchCheckpoint`):
   completed (candidate x fold) results journaled host-side, keyed by
   the structural grid signature, so a killed multi-hour search
   resumes past its finished work. ``SKDIST_CHECKPOINT_DIR`` or
   ``fit(..., checkpoint_dir=...)`` opt in.

4. **Injection seam** (:func:`set_injector`): the deterministic hook
   ``skdist_tpu.testing.faultinject`` installs to raise/poison/hang at
   chosen rounds. ``None`` (the default) costs one attribute read per
   ROUND — nothing per task.

Serving reuses the same taxonomy for its dispatch watchdog and
per-version :class:`CircuitBreaker` (``serve.engine``).
"""

import json
import logging
import os
import threading
import time

import numpy as np

__all__ = [
    "TRANSIENT",
    "PREEMPTED",
    "OOM",
    "WATCHDOG",
    "FATAL",
    "classify",
    "is_retryable",
    "RetryPolicy",
    "WatchdogTimeout",
    "CircuitBreaker",
    "nonfinite_lanes",
    "guard_enabled",
    "SearchCheckpoint",
    "grid_signature",
    "resolve_checkpoint_dir",
    "set_injector",
    "active_injector",
    "log_suppressed",
    "snapshot",
    "reset_stats",
]

logger = logging.getLogger("skdist_tpu.faults")

# ---------------------------------------------------------------------------
# taxonomy
# ---------------------------------------------------------------------------

#: retryable device/runtime hiccup (XLA UNAVAILABLE/INTERNAL/ABORTED,
#: broken transport): the round's host inputs are intact, re-dispatch
TRANSIENT = "transient"
#: a worker/device was preempted: retryable, but device state (placed
#: shared args) must be assumed lost and re-placed first
PREEMPTED = "preempted"
#: RESOURCE_EXHAUSTED: NOT retried here — the round loop's dedicated
#: shrink-and-resume machinery owns this kind
OOM = "oom"
#: a dispatch exceeded its watchdog budget (serving taxonomy; the
#: offline round loop treats a raised WatchdogTimeout as retryable)
WATCHDOG = "watchdog"
#: everything else: user/code errors — never retried, never swallowed
FATAL = "fatal"


class WatchdogTimeout(RuntimeError):
    """A dispatch ran past its watchdog budget."""


#: message fragments marking a transient runtime failure. XLA runtime
#: errors surface as jaxlib XlaRuntimeError whose str() carries the
#: absl status code; matching the code strings avoids importing jaxlib
#: internals and also covers transport-level errors raised as plain
#: RuntimeErrors by a worker's socket.
_TRANSIENT_MARKS = (
    "UNAVAILABLE",
    "ABORTED",
    "INTERNAL",
    "DATA_LOSS",
    "connection reset",
    "socket closed",
    "failed to connect",
    "Broken pipe",
)
_PREEMPT_MARKS = ("preempt", "PREEMPT", "worker has been restarted")
#: lower-cased fragments of a COMPILE-time failure. Mosaic and XLA word
#: a kernel or program the compiler refuses as "INTERNAL: Mosaic failed
#: to compile TPU kernel ..." / "INTERNAL: ... compilation ...": the
#: same program fails the same way on every re-dispatch, so it is a
#: code error, not a hiccup of the runtime.
_COMPILE_MARKS = ("mosaic", "compil")


def classify(exc):
    """Map an exception to its fault kind (module constants).

    Order matters: RESOURCE_EXHAUSTED is checked first so the OOM
    resume machinery always wins (some runtimes phrase it
    "INTERNAL: ... RESOURCE_EXHAUSTED"), then preemption (its messages
    often also carry UNAVAILABLE), then compile-time INTERNAL (fatal:
    never retried), then the transient marks — a run-time INTERNAL
    among them.
    """
    if isinstance(exc, WatchdogTimeout):
        return WATCHDOG
    msg = str(exc)
    if "RESOURCE_EXHAUSTED" in msg:
        return OOM
    if any(m in msg for m in _PREEMPT_MARKS):
        return PREEMPTED
    if "INTERNAL" in msg:
        low = msg.lower()
        if any(m in low for m in _COMPILE_MARKS):
            return FATAL
    if any(m in msg for m in _TRANSIENT_MARKS):
        return TRANSIENT
    return FATAL


def is_retryable(kind):
    """Whether the round loop may re-dispatch on this fault kind."""
    return kind in (TRANSIENT, PREEMPTED, WATCHDOG)


# ---------------------------------------------------------------------------
# retry policy
# ---------------------------------------------------------------------------

class RetryPolicy:
    """Bounded exponential backoff for round-granular retries.

    ``max_retries`` bounds CONSECUTIVE re-dispatches of one round (the
    counter resets when the task offset advances — progress proves the
    fault was transient); ``backoff_ms`` is the first delay, doubling
    per consecutive attempt up to ``max_backoff_ms``. Defaults come
    from ``SKDIST_ROUND_RETRIES`` (2) and ``SKDIST_RETRY_BACKOFF_MS``
    (50). ``max_retries=0`` disables retrying (every classified fault
    re-raises), which is also the forced policy on multi-process
    meshes — a locally caught exception cannot be re-synchronised with
    peers already inside the next collective.

    Jitter is OPT-IN (``jitter_ms`` / ``SKDIST_RETRY_JITTER_MS``,
    default 0): one process re-dispatching onto its own mesh has no
    thundering-herd peer, and the jitter-free default keeps the
    fault-injection matrix bitwise-checkable. A FLEET of replicas or
    hosts retrying against one shared resource (coordinator, storage,
    the recovering device pool itself) is exactly where synchronized
    retry storms come from — there, a uniform extra delay in
    ``[0, jitter_ms)`` per attempt decorrelates the herd. The jitter
    rides ON TOP of :meth:`delay_s` (which stays deterministic — it is
    what tests and log lines reason about); only the actual sleep
    moves.
    """

    __slots__ = ("max_retries", "backoff_ms", "max_backoff_ms", "_sleep",
                 "jitter_ms", "_rng")

    def __init__(self, max_retries=None, backoff_ms=None,
                 max_backoff_ms=5000.0, sleep=time.sleep,
                 jitter_ms=None, rng=None):
        if max_retries is None:
            max_retries = _env_int("SKDIST_ROUND_RETRIES", 2)
        if backoff_ms is None:
            backoff_ms = _env_float("SKDIST_RETRY_BACKOFF_MS", 50.0)
        if jitter_ms is None:
            jitter_ms = _env_float("SKDIST_RETRY_JITTER_MS", 0.0)
        self.max_retries = max(0, int(max_retries))
        self.backoff_ms = max(0.0, float(backoff_ms))
        self.max_backoff_ms = float(max_backoff_ms)
        self.jitter_ms = max(0.0, float(jitter_ms))
        self._sleep = sleep
        self._rng = rng  # lazily a random.Random; injectable for tests

    def delay_s(self, attempt):
        """Deterministic backoff before consecutive attempt ``attempt``
        (1-based) — excludes jitter by design (class docstring)."""
        ms = min(self.backoff_ms * (2.0 ** (attempt - 1)),
                 self.max_backoff_ms)
        return ms / 1e3

    def jitter_s(self):
        """One draw of the opt-in decorrelation delay: uniform in
        ``[0, jitter_ms)`` seconds; exactly 0.0 when jitter is off (the
        default — no RNG is even constructed, so injection runs stay
        bitwise-checkable)."""
        if self.jitter_ms <= 0.0:
            return 0.0
        if self._rng is None:
            import random

            self._rng = random.Random()
        return self._rng.uniform(0.0, self.jitter_ms) / 1e3

    def backoff(self, attempt):
        d = self.delay_s(attempt) + self.jitter_s()
        if d > 0:
            self._sleep(d)
        return d


def _env_int(name, default):
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        logger.warning("ignoring non-integer %s=%r", name, raw)
        return default


def _env_float(name, default):
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        logger.warning("ignoring non-numeric %s=%r", name, raw)
        return default


# ---------------------------------------------------------------------------
# counters (test/smoke observability) — backed by the process-wide
# telemetry registry (``skdist_tpu.obs.metrics``): one labeled family,
# ``faults.events{kind=...}``. record/snapshot/reset_stats stay the
# module's API; snapshot() is now a VIEW over the registry, so the same
# numbers surface through the Prometheus/JSON exporters with no second
# bookkeeping path.
# ---------------------------------------------------------------------------

_LOCK = threading.RLock()

#: the taxonomy of fault-layer events; an unknown name in record() is
#: a bug and raises (the old dict's KeyError contract)
FAULT_COUNTERS = (
    "rounds_retried",       # re-dispatches after a retryable fault
    "rounds_refused",       # rounds and probe compiles the device
                            # refused for memory, absorbed by a smaller
                            # round or the classic fallback
    "retries_exhausted",    # faults that ran out of policy budget
    "shared_replacements",  # shared-arg re-placements (preemption)
    "lanes_quarantined",    # tasks mapped to error_score by the guard
    "lanes_rung_killed",    # tasks retired early by an adaptive rung
    "suppressed",           # exceptions logged instead of swallowed
    "checkpoint_hits",      # tasks skipped because a journal had them
    "watchdog_trips",       # dispatches past their watchdog budget
    "elastic_shrinks",      # mesh rebuilt over survivors (preemption)
    "elastic_regrows",      # mesh re-grown after capacity returned
    "elastic_tasks_salvaged",  # tasks NOT re-run across an elastic
                               # shrink (journaled/gathered prefix)
    "replica_failovers",    # requests re-routed off a sick replica
    "shard_restages",       # catalog shards re-staged on a new holder
                            # after every assigned holder went down
    "replica_respawns",     # serving replicas drained + respawned
    "replica_proc_restarts",  # replica CHILD PROCESSES respawned by
                              # the procfleet supervisor
    "heartbeat_misses",     # supervisor heartbeats a replica missed
    "crash_loop_parks",     # replicas parked after N deaths in window
    "elastic_epoch_agreements",  # coordinated multi-process resumes
                                 # agreed (epoch, prefix, roster)
)


_EVENTS = None


def _events():
    global _EVENTS
    if _EVENTS is None:
        from ..obs import metrics as obs_metrics

        _EVENTS = obs_metrics.counter(
            "faults.events", help="fault-layer events by kind"
        )
    return _EVENTS


def record(counter, n=1):
    if counter not in FAULT_COUNTERS:
        raise KeyError(f"unknown fault counter {counter!r}")
    _events().inc(int(n), kind=counter)
    # every fault-layer event also lands in the flight recorder's
    # bounded ring: an incident file's last-seconds story is mostly
    # made of these (fault events are rare by construction — this is
    # one dict append, never I/O)
    from ..obs import flightrec

    flightrec.note("fault", event=counter, n=int(n))
    if counter == "retries_exhausted":
        # the round loop is about to fail loud: freeze the story now,
        # while the raising stack still exists
        flightrec.dump_incident("retries_exhausted")


def snapshot():
    # one children() read = one lock acquisition, so the returned
    # counters are mutually consistent (the old single-dict guarantee)
    kids = _events().children()
    return {
        k: int(kids.get((("kind", k),), 0)) for k in FAULT_COUNTERS
    }


def reset_stats():
    _events().reset()


_SUPPRESSED_SEEN = set()


def log_suppressed(where, exc, level=logging.WARNING):
    """The replacement for a bare ``except Exception: pass``: count and
    log what was swallowed. First occurrence per (site, exception type)
    logs at ``level``; repeats drop to DEBUG so a flaky probe cannot
    flood the log at fleet scale."""
    record("suppressed")
    key = (where, type(exc).__name__)
    with _LOCK:
        first = key not in _SUPPRESSED_SEEN
        if first:
            _SUPPRESSED_SEEN.add(key)
    logger.log(
        level if first else logging.DEBUG,
        "suppressed %s in %s: %s", type(exc).__name__, where, exc,
    )


# ---------------------------------------------------------------------------
# lane quarantine
# ---------------------------------------------------------------------------

def guard_enabled():
    """The non-finite lane guard is ON by default;
    ``SKDIST_FAULT_GUARD=0`` is the kill switch (e.g. for workloads
    whose legitimate outputs contain inf)."""
    return os.environ.get("SKDIST_FAULT_GUARD", "").strip().lower() not in (
        "0", "false", "no",
    )


def nonfinite_lanes(tree):
    """Boolean mask over the leading (task) axis marking lanes with ANY
    non-finite value in ANY leaf, or None when everything is finite
    (the fast path: one ``np.isfinite().all()`` per leaf, no mask
    allocation). Host-side numpy on already-gathered outputs — adds no
    device work and no compiles."""
    import jax

    mask = None
    for leaf in jax.tree_util.tree_leaves(tree):
        arr = np.asarray(leaf)
        if not np.issubdtype(arr.dtype, np.floating):
            continue
        finite = np.isfinite(arr)
        if finite.all():
            continue
        lane_bad = ~finite.reshape(arr.shape[0], -1).all(axis=1)
        mask = lane_bad if mask is None else (mask | lane_bad)
    return mask


# ---------------------------------------------------------------------------
# circuit breaker (serving: per model-version dispatch health)
# ---------------------------------------------------------------------------

class CircuitBreaker:
    """Per-key consecutive-failure circuit breaker (serving taxonomy).

    A key (the serving engine uses ``name@version``) opens after
    ``threshold`` consecutive classified faults; while open,
    :meth:`allow` rejects immediately — the engine turns that into a
    typed ``CircuitOpen`` so callers shed load onto a healthy version
    instead of queueing against a sick one. After ``cooldown_s`` the
    breaker goes half-open: ONE probe request is admitted, and its
    outcome closes or re-opens the circuit. Thread-safe; fully
    in-memory.
    """

    def __init__(self, threshold=3, cooldown_s=30.0, clock=time.monotonic):
        self.threshold = max(1, int(threshold))
        self.cooldown_s = float(cooldown_s)
        self._clock = clock
        self._lock = threading.Lock()
        # key -> [consecutive_failures, opened_at or None,
        #         probe_started_at or None]
        self._state = {}

    def _ent(self, key):
        ent = self._state.get(key)
        if ent is None:
            ent = self._state[key] = [0, None, None]
        return ent

    def allow(self, key):
        """True if a request for ``key`` may proceed (closed circuit,
        or the single half-open probe). A probe whose outcome was never
        reported (e.g. its request was shed for an unrelated reason
        before dispatch) expires after another cooldown, so an
        abandoned probe cannot latch the circuit open forever."""
        with self._lock:
            ent = self._ent(key)
            now = self._clock()
            if ent[1] is None:
                return True
            if now - ent[1] < self.cooldown_s:
                return False
            if ent[2] is not None and now - ent[2] < self.cooldown_s:
                return False  # a live probe is already in flight
            ent[2] = now
            return True

    def record_success(self, key):
        with self._lock:
            self._state[key] = [0, None, None]

    def record_failure(self, key, kind=FATAL):
        """Returns True when this failure OPENED the circuit."""
        with self._lock:
            ent = self._ent(key)
            ent[0] += 1
            ent[2] = None
            if ent[1] is not None:
                # failed half-open probe: stay open, restart cooldown
                ent[1] = self._clock()
                return False
            if ent[0] >= self.threshold:
                ent[1] = self._clock()
                return True
            return False

    def state(self, key):
        """'closed' | 'open' | 'half-open' for observability."""
        with self._lock:
            ent = self._state.get(key)
            if ent is None or ent[1] is None:
                return "closed"
            if self._clock() - ent[1] >= self.cooldown_s:
                return "half-open"
            return "open"

    def states(self):
        with self._lock:
            keys = list(self._state)
        return {k: self.state(k) for k in keys}


# ---------------------------------------------------------------------------
# durable search checkpoints
# ---------------------------------------------------------------------------

def resolve_checkpoint_dir(explicit=None):
    """The checkpoint directory: the explicit ``fit`` argument wins,
    else ``SKDIST_CHECKPOINT_DIR``, else None (checkpointing off)."""
    if explicit:
        return str(explicit)
    env = os.environ.get("SKDIST_CHECKPOINT_DIR", "").strip()
    return env or None


def _digest_update_array(h, arr):
    """Feed an array's identity into a hash: shape + dtype always, and
    a bounded byte sample (head + tail slabs) so signatures stay O(MB)
    even for multi-GB training sets. A sampled signature can collide
    only for arrays agreeing on shape, dtype, and both slabs — at
    which point resuming into the journal is the user mixing
    deliberately near-identical data, not an accident the full hash
    would catch either."""
    arr = np.ascontiguousarray(arr)
    h.update(repr((arr.shape, str(arr.dtype))).encode())
    raw = arr.view(np.uint8).reshape(-1)
    slab = 1 << 20
    if raw.nbytes <= 2 * slab:
        h.update(raw.tobytes())
    else:
        h.update(raw[:slab].tobytes())
        h.update(raw[-slab:].tobytes())


def data_digest(X):
    """Stable digest of a training input (dense, pandas, scipy sparse,
    or any object exposing ``content_digest()`` — e.g. a
    ``ChunkedDataset``, whose digest covers its meta + head/tail block
    samples without materialising the out-of-core matrix) for the grid
    signature."""
    import hashlib

    digest = getattr(X, "content_digest", None)
    if callable(digest):
        return str(digest())
    h = hashlib.blake2b(digest_size=16)
    if hasattr(X, "values") and not isinstance(X, np.ndarray):
        X = X.values
    if hasattr(X, "data") and hasattr(X, "indptr"):  # CSR/CSC
        h.update(repr((type(X).__name__, X.shape)).encode())
        _digest_update_array(h, np.asarray(X.data))
        _digest_update_array(h, np.asarray(X.indptr))
    else:
        arr = np.asarray(X)
        if arr.dtype == object:
            # same head+tail sampling contract as the dense slabs in
            # _digest_update_array: shape always, then both ends, so a
            # regenerated tail (or truncation) changes the signature
            h.update(repr((arr.shape,)).encode())
            flat = arr.reshape(-1)
            if flat.size <= 128:
                h.update(repr(flat.tolist()).encode())
            else:
                h.update(repr(flat[:64].tolist()).encode())
                h.update(repr(flat[-64:].tolist()).encode())
        else:
            _digest_update_array(h, arr)
    return h.hexdigest()


def grid_signature(*parts):
    """Hex digest of the STRUCTURAL identity of one search: estimator
    class, candidate params, CV geometry, scoring config, data digests
    — anything that changes the meaning of task id ``t``. Same recipe
    as the compile cache's structural keys (PR-1): canonical reprs,
    never object identities, so the signature survives a process
    restart."""
    import hashlib

    h = hashlib.blake2b(digest_size=16)
    for p in parts:
        h.update(repr(p).encode())
        h.update(b"\x00")
    return h.hexdigest()


class SearchCheckpoint:
    """Append-only journal of completed (candidate x fold) tasks.

    One JSONL file per grid signature under ``checkpoint_dir``; each
    line is ``{"t": task_id, "r": {score dict}}``. Opening loads every
    complete line (a half-written tail from a SIGKILL mid-append is
    dropped, not fatal) into :attr:`completed`; :meth:`record` appends
    + flushes, so what a killed process loses is bounded by one round.
    Floats ride JSON's shortest-round-trip repr — reloaded scores are
    bitwise what was journaled. Thread-safe (the host fan-out records
    from worker threads).
    """

    def __init__(self, checkpoint_dir, signature):
        self.signature = str(signature)
        self.path = os.path.join(
            checkpoint_dir, f"skdist-ckpt-{self.signature}.jsonl"
        )
        self._lock = threading.Lock()
        os.makedirs(checkpoint_dir, exist_ok=True)
        self.completed = {}
        self._load()
        self._fh = open(self.path, "a", encoding="utf-8")

    def _load(self):
        if not os.path.exists(self.path):
            return
        with open(self.path, "r", encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                    self.completed[int(row["t"])] = row["r"]
                except (ValueError, KeyError, TypeError):
                    # torn tail write from a kill mid-append: the task
                    # simply reruns
                    continue
        if self.completed:
            record("checkpoint_hits", len(self.completed))
            logger.info(
                "checkpoint %s: resuming past %d completed tasks",
                self.path, len(self.completed),
            )

    def record(self, task_id, scores):
        """Journal one completed task (scores: flat dict of floats)."""
        row = json.dumps(
            {"t": int(task_id), "r": {k: float(v) for k, v in scores.items()}}
        )
        with self._lock:
            self.completed[int(task_id)] = scores
            self._fh.write(row + "\n")
            self._fh.flush()

    def record_many(self, pairs):
        for task_id, scores in pairs:
            self.record(task_id, scores)

    def close(self):
        with self._lock:
            try:
                self._fh.close()
            except Exception as exc:
                log_suppressed("SearchCheckpoint.close", exc,
                               level=logging.DEBUG)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# ---------------------------------------------------------------------------
# injection seam
# ---------------------------------------------------------------------------

_INJECTOR = None


def set_injector(inj):
    """Install (or with None, remove) the process-wide fault injector
    consulted by the round loop. Test/harness API — see
    ``skdist_tpu.testing.faultinject``. Returns the previous one."""
    global _INJECTOR
    prev = _INJECTOR
    _INJECTOR = inj
    return prev


def active_injector():
    return _INJECTOR
