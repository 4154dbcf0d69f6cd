"""
ProcessReplicaSet: serving replicas as supervised OS child processes —
real fault domains behind a unix-domain-socket front door.

:class:`~skdist_tpu.serve.replicaset.ReplicaSet` (PR 8) heals engines
*inside one process*: a segfault in a kernel, an unkillable wedged
device op, or an OOM-kill
still takes down every replica at once, because they share a process.
The reference world never had this problem — Spark gave sk-dist
executor JVMs as fault domains, with the driver surviving any worker
death — and Clipper (Crankshaw et al., NSDI'17) isolates model
containers behind an RPC front door for exactly this reason. This
module is that layer natively:

- **replicas are child processes**: each replica is a full
  :class:`~skdist_tpu.serve.engine.ServingEngine` running in its own
  OS process (``serve.procworker``), listening on a unix-domain
  socket. The parent holds a thin client pool per replica; requests
  are length-prefixed pickled frames (:func:`send_frame` /
  :func:`recv_frame`). A replica death is a process death — it cannot
  corrupt the router or its siblings.

- **the supervisor owns liveness**: a background thread heartbeats
  every replica (a ``ping`` frame with a reply deadline).
  ``miss_threshold`` consecutive missed beats declare the replica
  dead — a wedged or SIGSTOPped child that still *owns* its socket is
  treated exactly like one that crashed — and the whole process GROUP
  is SIGKILLed (the ``childproc.py`` containment recipe: the child is
  spawned ``start_new_session`` so grandchildren die with it).

- **bounded-backoff respawn + crash-loop parking**: a dead replica is
  respawned after an exponential backoff (``respawn_backoff_s``
  doubling per consecutive death). ``crash_loop_threshold`` deaths
  inside ``crash_loop_window_s`` PARK the replica instead — a replica
  that cannot hold a process up must not burn the host spawning it in
  a loop. :class:`AllReplicasUnhealthy` surfaces only when the whole
  fleet is parked (or nothing comes back within the bounded
  unhealthy wait); a fleet with any respawn still pending briefly
  queues instead.

- **graceful drain**: ``close()`` / :meth:`stop_replica` SIGTERM the
  worker, which stops admissions, drains its queued flushes, and
  exits 0; only a worker that overstays ``drain_timeout_s`` is
  SIGKILLed. :meth:`rolling_restart` drains+respawns one replica at a
  time so the fleet serves throughout — the operational rendition of
  "config rollout without downtime".

- **0-compile respawns**: replicas share ``artifact_dir`` — the PR-1
  on-disk ``jax.export`` AOT tier — so a respawned process's
  re-registration (the parent replays every published
  ``name@version``, numbering preserved) prewarms from disk instead
  of XLA and serves its first request with zero compiles.

Routing, failover semantics, and stats mirror ``ReplicaSet``: least
loaded (parent-side in-flight + child queue depth from the last
heartbeat), request-owned verdicts (``ValueError`` / ``TypeError`` /
``KeyError`` / :class:`DeadlineExceeded`) surface, everything else
re-routes and feeds the health bookkeeping. Deterministic injection:
``FaultInjector.kill_replica_proc(i, at_request=k)`` and
``stall_replica_proc`` (SIGSTOP — heartbeat-stall) are consulted on
every routed request ordinal, so "replica 1 is SIGKILLed at request
60 under load" is an exact, replayable sentence
(``build_tools/procfleet_smoke.py``).

- **the supervisor owns fleet observability** (PR 15): workers answer
  a ``telemetry`` op with their full metrics-registry dump, scoped
  compile delta, trace ring, and flight-recorder ring; the supervisor
  merges them into ONE fleet registry (``replica``/``pid`` labels,
  Prometheus-federation shape) behind :meth:`fleet_metrics_text` /
  :meth:`fleet_json_snapshot`, stitches per-process trace rings into
  one Perfetto file (:meth:`export_fleet_trace` — worker flush spans
  parent under the router's ``route`` spans via the shipped trace
  context), writes a timestamped INCIDENT file on every replica
  death / crash-loop park / ``AllReplicasUnhealthy`` (embedding the
  dead child's last standing flight-recorder snapshot — the SIGKILL
  post-mortem), and optionally serves it all on the stdlib ops
  endpoint (``obs_port=`` / ``SKDIST_OBS_PORT``: ``/metrics``,
  ``/healthz``, ``/debug/flightrec``). A replica whose harvest fails
  — dead mid-RPC, parked, or answering an older frame schema —
  degrades to its LAST harvested state marked by the
  ``skdist_stale{replica=...}`` gauge instead of failing ``stats()``
  or the exposition. ``SKDIST_OBS_HARVEST=0`` disables the periodic
  harvest entirely.

The wire protocol is pickle over a parent-owned unix socket: a
same-host, same-user trust boundary (the socket lives in a
``mkdtemp`` directory), not a network protocol.
"""

import json
import os
import pickle
import signal
import socket
import struct
import subprocess
import sys
import tempfile
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FutureTimeout

import numpy as np

from ..obs import export as obs_export
from ..obs import flightrec as obs_flightrec
from ..obs import httpd as obs_httpd
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..parallel import faults
from ..utils.childproc import _kill_group
from .batcher import (
    CircuitOpen,
    DeadlineExceeded,
    Overloaded,
    ServingError,
)
from .replicaset import (
    AllReplicasUnhealthy,
    _rendezvous_holders,
    _stable_hash,
    fleet_by_model,
)
from .shm import DEFAULT_SLOT_BYTES, DEFAULT_SLOTS, ShmRing, shm_enabled

__all__ = [
    "ProcessReplicaSet",
    "ReplicaError",
    "ReplicaConnectionError",
    "WireError",
    "FrameTooLarge",
    "send_frame",
    "recv_frame",
    "TELEMETRY_SCHEMA",
]

#: version tag of the ``telemetry`` op's reply frame; a worker
#: answering a DIFFERENT schema (a mixed-version fleet mid-upgrade)
#: degrades to stale-marked, never to a parse crash in the supervisor
TELEMETRY_SCHEMA = 1


def harvest_enabled():
    """The periodic telemetry harvest is ON by default;
    ``SKDIST_OBS_HARVEST=0`` is the kill switch (also the baseline leg
    of the harvest-overhead smoke gate)."""
    return os.environ.get("SKDIST_OBS_HARVEST", "").strip().lower() not in (
        "0", "false", "no",
    )


#: HELP lines for the supervisor-side transport families — pinned by
#: the obs conformance tests so the fleet exposition self-documents
_TRANSPORT_HELP = {
    "serve.shm_bytes": "payload bytes carried over shared-memory ring "
                       "slots instead of pickled frames",
    "serve.shm_fallbacks": "requests that wanted the ring but fell back "
                           "to a pickled frame (ring full, payload over "
                           "slot_bytes, or a pickled reply)",
    "serve.frames_pickled": "request round trips whose payload rode the "
                            "classic pickled frame (no ring, fallback, "
                            "or non-numeric payload)",
}


def _transport_counter(name):
    return obs_metrics.registry().counter(
        name, help=_TRANSPORT_HELP.get(name, "")
    )


# ---------------------------------------------------------------------------
# wire protocol: length-prefixed pickled frames
# ---------------------------------------------------------------------------

_FRAME_HEADER = struct.Struct(">I")
#: upper bound on one frame — far above any sane request, far below a
#: length that would make a corrupted header allocate the host away
MAX_FRAME_BYTES = 1 << 30


class WireError(ServingError):
    """Framing/transport violation on the front-door socket: truncated
    header, oversized length, undecodable payload, or a peer closing
    mid-frame. The stream cannot be resynchronised past it — the
    connection is abandoned (the replica itself keeps serving its
    other connections)."""


class FrameTooLarge(ValueError):
    """A LOCALLY-built frame exceeds the wire bound. Deliberately a
    ``ValueError``, NOT a :class:`WireError`: nothing touched the
    socket, so this is a request-owned verdict that must surface to
    the caller — conflating it with transport death would get every
    healthy replica serially declared dead over one oversized
    request."""


class ReplicaError(ServingError):
    """A replica-side failure with no local exception type — always
    failover-worthy (the verdict is about the replica, not the
    request)."""


class ReplicaConnectionError(ReplicaError):
    """The replica's socket died mid-conversation — the strongest
    process-death signal the router sees before the supervisor's
    heartbeat confirms it."""


def send_frame(sock, obj):
    """Write one length-prefixed pickled frame. An over-bound payload
    raises :class:`FrameTooLarge` BEFORE touching the socket."""
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    if len(payload) > MAX_FRAME_BYTES:
        raise FrameTooLarge(
            f"frame of {len(payload)} bytes exceeds the "
            f"{MAX_FRAME_BYTES}-byte bound; bulk payloads belong on "
            "distribute.batch_predict, not the online front door"
        )
    sock.sendall(_FRAME_HEADER.pack(len(payload)) + payload)


def recv_frame(sock):
    """Read one frame; raises :class:`WireError` on EOF mid-frame, an
    oversized length prefix, or an undecodable payload."""
    return recv_frame_timed(sock)[0]


def recv_frame_timed(sock):
    """:func:`recv_frame` plus the TRANSPORT seconds it spent: the
    body read + unpickle AFTER the 4-byte header arrived. The header
    wait is the peer's compute time, deliberately excluded — this is
    what the wirespeed smoke's transport-overhead gate measures."""
    (n,) = _FRAME_HEADER.unpack(_recv_exact(sock, _FRAME_HEADER.size))
    if n > MAX_FRAME_BYTES:
        raise WireError(
            f"frame length {n} exceeds the {MAX_FRAME_BYTES}-byte bound "
            "(corrupted header?)"
        )
    t0 = time.perf_counter()
    payload = _recv_exact(sock, n)
    try:
        obj = pickle.loads(payload)
    except Exception as exc:
        raise WireError(f"undecodable frame: {exc!r}") from exc
    return obj, time.perf_counter() - t0


def _recv_exact(sock, n):
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(min(n - len(buf), 1 << 20))
        if not chunk:
            raise WireError("socket closed mid-frame")
        buf += chunk
    return bytes(buf)


#: replica-side exception types reconstructed BY NAME in the parent so
#: failover semantics survive the process boundary (anything else
#: becomes a failover-worthy ReplicaError)
_TYPED_ERRORS = {
    cls.__name__: cls
    for cls in (
        ValueError, TypeError, KeyError, RuntimeError,
        ServingError, Overloaded, DeadlineExceeded, CircuitOpen,
        faults.WatchdogTimeout, FrameTooLarge,
    )
}


def encode_error(exc):
    """Worker-side: one exception as a reply frame."""
    return {"ok": False, "etype": type(exc).__name__, "msg": str(exc)}


def decode_error(reply):
    """Parent-side: rebuild the typed exception (or a
    :class:`ReplicaError` for unknown types)."""
    cls = _TYPED_ERRORS.get(reply.get("etype"))
    msg = reply.get("msg", "")
    if cls is None:
        return ReplicaError(f"{reply.get('etype')}: {msg}")
    return cls(msg)


# ---------------------------------------------------------------------------
# client pool
# ---------------------------------------------------------------------------

class _ClientPool:
    """Per-replica connection pool: one RPC owns one connection for its
    round trip (frames never interleave); idle connections are reused.
    Any socket/framing error abandons the connection and surfaces as
    :class:`ReplicaConnectionError` — the router's process-death
    signal."""

    def __init__(self, path, connect_timeout_s=5.0):
        self.path = path
        self.connect_timeout_s = connect_timeout_s
        self._lock = threading.Lock()
        self._idle = []
        self._closed = False

    def _get(self):
        with self._lock:
            if self._closed:
                raise ReplicaConnectionError("client pool is closed")
            if self._idle:
                return self._idle.pop()
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            s.settimeout(self.connect_timeout_s)
            s.connect(self.path)
        except OSError as exc:
            s.close()
            raise ReplicaConnectionError(
                f"cannot connect to replica socket {self.path}: {exc}"
            ) from exc
        return s

    def _put(self, conn):
        with self._lock:
            if not self._closed:
                self._idle.append(conn)
                return
        conn.close()

    def request(self, op, payload, timeout_s):
        """One RPC round trip. Returns the reply value or raises the
        decoded typed exception; transport failures raise
        :class:`ReplicaConnectionError`."""
        reply, _wire_s = self.request_raw(op, payload, timeout_s)
        if reply.get("ok"):
            return reply.get("value")
        raise decode_error(reply)

    def request_raw(self, op, payload, timeout_s):
        """One round trip returning ``(reply_dict, wire_seconds)`` —
        the RAW reply frame (the shm data plane routes on its ``shm``
        key before any value decode) plus the transport seconds spent
        serializing/sending the request and reading/decoding the reply
        body (the peer's compute wait excluded)."""
        conn = self._get()
        try:
            conn.settimeout(timeout_s)
            t0 = time.perf_counter()
            send_frame(conn, (op, payload))
            send_s = time.perf_counter() - t0
            reply, recv_s = recv_frame_timed(conn)
        except (OSError, WireError, EOFError) as exc:
            try:
                conn.close()
            except OSError:
                pass
            raise ReplicaConnectionError(
                f"replica RPC {op!r} failed: {exc}"
            ) from exc
        self._put(conn)
        if not isinstance(reply, dict):
            raise ReplicaConnectionError(
                f"replica RPC {op!r} returned a non-reply frame"
            )
        return reply, send_s + recv_s

    def close(self):
        with self._lock:
            self._closed = True
            idle, self._idle = self._idle, []
        for c in idle:
            try:
                c.close()
            except OSError:
                pass


# ---------------------------------------------------------------------------
# the fleet
# ---------------------------------------------------------------------------

class _ProcReplica:
    """One fleet member: the child process plus the supervisor's view."""

    __slots__ = (
        "index", "generation", "proc", "socket_path", "log_path", "pool",
        "alive", "parked", "draining", "misses", "failures", "routed",
        "in_flight", "queue_depth", "deaths", "consecutive_deaths",
        "respawn_due_at", "death_reason", "intentional_stop",
        "flightrec_path", "telemetry_state", "telemetry_pid",
        "telemetry_compiles", "telemetry_stale", "trace_part",
        "flightrec_events", "ring",
    )

    def __init__(self, index):
        self.index = index
        self.generation = 0
        self.proc = None
        self.socket_path = None
        self.log_path = None
        self.pool = None
        self.alive = False
        self.parked = False
        self.draining = False
        self.misses = 0
        self.failures = 0      # consecutive failover-worthy failures
        self.routed = 0
        self.in_flight = 0
        self.queue_depth = 0   # from the last heartbeat reply
        self.deaths = deque()  # wall times, crash-loop accounting
        self.consecutive_deaths = 0
        self.respawn_due_at = None
        self.death_reason = None
        self.intentional_stop = False
        #: the worker's standing flight-recorder file (stable across
        #: generations: the supervisor reads a dead child's last
        #: snapshot from it)
        self.flightrec_path = None
        #: last successful telemetry harvest: registry dump / pid /
        #: scoped compile delta / trace part / flight-recorder ring.
        #: ``telemetry_stale`` starts True (nothing harvested yet) and
        #: flips on each harvest outcome — a failed harvest KEEPS the
        #: old state and only marks it stale
        self.telemetry_state = None
        self.telemetry_pid = None
        self.telemetry_compiles = None
        self.telemetry_stale = True
        self.trace_part = None
        self.flightrec_events = None
        #: the shared-memory data plane of the CURRENT generation
        #: (supervisor-owned ``serve.shm.ShmRing``); fresh per spawn,
        #: closed+unlinked by the supervisor on every death — a
        #: SIGKILLed worker can never leak /dev/shm
        self.ring = None

    @property
    def pid(self):
        return self.proc.pid if self.proc is not None else None


class ProcessReplicaSet:
    """Supervised multi-process serving fleet (module docstring).

    ``engine_kwargs`` (JSON-able) configure each worker's
    ``ServingEngine``; ``backend_spec`` its backend (``None`` →
    ``{"kind": "tpu"}`` — a ``TPUBackend`` over the worker's visible
    devices; ``{"kind": "tpu", "kwargs": {...}}`` passes constructor
    kwargs, e.g. per-replica device subsets via env in
    ``worker_env``). ``artifact_dir`` points every worker at one
    shared on-disk AOT artifact tier so respawns compile nothing.
    ``worker_argv`` is the spawn seam: a callable ``(index,
    socket_path, config_json) -> argv`` replacing the default
    ``python -m skdist_tpu.serve.procworker`` line (deployments wrap
    it in numactl/env shims; tests substitute crashing workers).
    """

    def __init__(self, n_replicas=2, artifact_dir=None, engine_kwargs=None,
                 backend_spec=None, worker_argv=None, worker_env=None,
                 heartbeat_interval_s=0.5, heartbeat_timeout_s=2.0,
                 miss_threshold=3, sick_threshold=3,
                 respawn_backoff_s=0.25, max_respawn_backoff_s=10.0,
                 crash_loop_window_s=30.0, crash_loop_threshold=3,
                 spawn_timeout_s=120.0, drain_timeout_s=15.0,
                 request_timeout_s=60.0, unhealthy_wait_s=30.0,
                 harvest_interval_s=2.0, obs_port=None,
                 incident_dir=None, shm_slots=DEFAULT_SLOTS,
                 shm_slot_bytes=DEFAULT_SLOT_BYTES):
        """Observability knobs on top of the fault-domain ones:
        ``harvest_interval_s`` paces the supervisor's periodic
        ``telemetry`` harvest (``SKDIST_OBS_HARVEST=0`` disables it;
        scrapes and :meth:`stats` refresh on demand either way);
        ``obs_port`` (default: ``SKDIST_OBS_PORT``; ``0`` = ephemeral)
        opts into the ops endpoint; ``incident_dir`` overrides where
        incident files land (default ``SKDIST_FLIGHTREC_DIR`` /
        ``<tmp>/skdist-flightrec`` — deliberately OUTSIDE the fleet's
        socket tempdir, which is removed on close).

        ``shm_slots`` × ``shm_slot_bytes`` size each replica's
        shared-memory ring (``serve.shm`` — the zero-copy data plane;
        the socket then carries only doorbell frames). ``shm_slots=0``
        — or ``SKDIST_SHM=0`` — disables the ring: every payload rides
        classic pickled frames."""
        if int(n_replicas) < 1:
            raise ValueError(f"n_replicas must be >= 1; got {n_replicas}")
        # resolve (and validate) the ops port BEFORE any worker spawns:
        # a malformed SKDIST_OBS_PORT must fail here, not after the
        # fleet is up (which would orphan the spawned processes)
        self._obs_port = obs_httpd.resolve_port(obs_port)
        self.artifact_dir = str(artifact_dir) if artifact_dir else None
        self.engine_kwargs = dict(engine_kwargs or {})
        self.backend_spec = backend_spec
        self._worker_argv = worker_argv
        self.worker_env = dict(worker_env or {})
        self.heartbeat_interval_s = float(heartbeat_interval_s)
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.miss_threshold = max(1, int(miss_threshold))
        self.sick_threshold = max(1, int(sick_threshold))
        self.respawn_backoff_s = float(respawn_backoff_s)
        self.max_respawn_backoff_s = float(max_respawn_backoff_s)
        self.crash_loop_window_s = float(crash_loop_window_s)
        self.crash_loop_threshold = max(1, int(crash_loop_threshold))
        self.spawn_timeout_s = float(spawn_timeout_s)
        self.drain_timeout_s = float(drain_timeout_s)
        self.request_timeout_s = request_timeout_s
        self.unhealthy_wait_s = float(unhealthy_wait_s)
        self.harvest_interval_s = float(harvest_interval_s)
        self.incident_dir = incident_dir
        self.shm_slots = int(shm_slots)
        self.shm_slot_bytes = int(shm_slot_bytes)
        #: per-means transport overhead ledger: mean seconds of
        #: serialize/send + reply read/decode + ring memcpys per
        #: request, split by which plane carried the payload —
        #: ``stats()["transport"]`` and the wirespeed smoke's >=5x gate
        self._transport = {"shm": [0, 0.0], "pickle": [0, 0.0]}

        self._dir = tempfile.mkdtemp(prefix="skpf-")
        self._lock = threading.Lock()
        self._respawn_lock = threading.Lock()
        self._closed = False
        self._requests = 0
        self._rr = 0
        #: rollout spec store, same contract as ReplicaSet._published:
        #: versions as the PARENT assigned them, replayed verbatim into
        #: every respawned generation
        self._published = {}
        #: bank-aware routing map, same contract as
        #: ReplicaSet._shard_of/_shard_holders (see rollout_many)
        self._shard_of = {}
        self._shard_holders = {}
        self._n_shards = 0
        self.events = []
        self._replicas = [_ProcReplica(i) for i in range(int(n_replicas))]
        self._executor = ThreadPoolExecutor(
            max_workers=max(4, 4 * int(n_replicas)),
            thread_name_prefix="skdist-procfleet",
        )
        #: respawns run on their OWN thread — never on the request
        #: executor, whose workers may all be parked in the
        #: "waiting for a respawn" loop (healing must not queue
        #: behind the traffic that is waiting on it), and never on
        #: the heartbeat thread (a slow spawn must not blind
        #: liveness detection for the other replicas)
        self._respawn_exec = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="skdist-procfleet-respawn",
        )
        for r in self._replicas:
            # standing flight-recorder file, STABLE across generations:
            # a dead generation's last snapshot is still there when the
            # supervisor builds the incident file
            r.flightrec_path = os.path.join(
                self._dir, f"r{r.index}.flightrec.json"
            )
        for r in self._replicas:
            try:
                self._spawn(r)
                r.alive = True
            except Exception as exc:
                # construction tolerates a failed spawn (incl. a Popen
                # OSError from a broken worker_argv): the supervisor
                # retries on backoff and crash-loop parking bounds it —
                # a fleet is built to outlive its members
                self._record_death(r, f"spawn: {exc}")
        self._stop_evt = threading.Event()
        self._supervisor = threading.Thread(
            target=self._supervise, daemon=True,
            name="skdist-procfleet-supervisor",
        )
        self._supervisor.start()
        self._harvester = None
        if self.harvest_interval_s > 0:
            self._harvester = threading.Thread(
                target=self._harvest_loop, daemon=True,
                name="skdist-procfleet-harvest",
            )
            self._harvester.start()
        self._obs_server = None
        port = self._obs_port
        if port is not None:
            try:
                self._obs_server = obs_httpd.OpsServer(
                    port=port,
                    metrics=lambda: self.fleet_metrics_text(refresh=True),
                    healthz=self._healthz,
                    flightrec=self._flightrec_doc,
                ).start()
            except OSError:
                # a taken port must not leak a spawned fleet: tear the
                # workers down before surfacing the bind failure
                self.close(drain=False)
                raise

    # ------------------------------------------------------------------
    # spawning
    # ------------------------------------------------------------------
    def _argv_for(self, r, sock_path):
        cfg = json.dumps({
            "engine": self.engine_kwargs,
            "backend": self.backend_spec,
            "artifact_dir": self.artifact_dir,
            "replica": r.index,
            "flightrec": r.flightrec_path,
            # the parent may have enabled tracing programmatically
            # (set_enabled) — the spawn carries the decision so the
            # worker's track isn't empty in the stitched fleet trace
            "trace": bool(obs_trace.enabled()),
            # the attach recipe for THIS generation's ring (None =
            # pickled frames only); the worker maps it, never owns it
            "shm": r.ring.describe() if r.ring is not None else None,
        })
        if self._worker_argv is not None:
            return list(self._worker_argv(r.index, sock_path, cfg))
        return [sys.executable, "-m", "skdist_tpu.serve.procworker",
                "--socket", sock_path, "--config", cfg]

    def _spawn(self, r):
        """Start one worker process and wait for its front door to
        answer a ping. Raises :class:`ServingError` on spawn failure
        (the caller records the death for crash-loop accounting)."""
        r.generation += 1
        sock_path = os.path.join(
            self._dir, f"r{r.index}g{r.generation}.sock"
        )
        r.log_path = os.path.join(self._dir, f"r{r.index}.log")
        # fresh ring per generation, created BEFORE the argv so the
        # config carries its attach recipe; any previous generation's
        # ring dies here even if the death path missed it
        if r.ring is not None:
            r.ring.close()
            r.ring = None
        if self.shm_slots > 0 and shm_enabled():
            r.ring = ShmRing.create(self.shm_slots, self.shm_slot_bytes)
        env = dict(os.environ)
        # the ops endpoint is the SUPERVISOR's: an inherited
        # SKDIST_OBS_PORT would have every worker fight it (and each
        # other) for the bind; worker_env may still set it explicitly
        env.pop("SKDIST_OBS_PORT", None)
        # the worker must resolve skdist_tpu the way the parent did
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        ))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
        env.update(self.worker_env)
        argv = self._argv_for(r, sock_path)
        with open(r.log_path, "ab") as log:
            # start_new_session: the worker owns a fresh process group,
            # so the supervisor's SIGKILL reaches its grandchildren too
            # (the childproc.py containment recipe)
            proc = subprocess.Popen(
                argv, start_new_session=True, env=env,
                stdout=log, stderr=subprocess.STDOUT,
            )
        r.proc = proc
        r.socket_path = sock_path
        r.pool = _ClientPool(sock_path)
        deadline = time.monotonic() + self.spawn_timeout_s
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise ServingError(
                    f"replica {r.index} worker exited rc={proc.returncode} "
                    f"before serving (log: {r.log_path})"
                )
            if os.path.exists(sock_path):
                try:
                    r.pool.request("ping", {}, 5.0)
                    r.misses = 0
                    return
                except ReplicaError:
                    pass
            time.sleep(0.05)
        _kill_group(proc)
        raise ServingError(
            f"replica {r.index} worker did not answer within "
            f"{self.spawn_timeout_s}s (log: {r.log_path})"
        )

    # ------------------------------------------------------------------
    # rollout
    # ------------------------------------------------------------------
    def rollout(self, name, model, methods=("predict",), version=None,
                serve_dtype="float32"):
        """Fleet-wide prewarm-before-publish: register (and prewarm)
        on EVERY routable replica, then publish. The PARENT assigns
        the version number and passes it explicitly, so every replica
        — and every future respawned generation — registers the same
        ``name@version``. Raises without publishing if any replica's
        registration fails."""
        if self._closed:
            raise ServingError("replica set is closed")
        methods = (methods,) if isinstance(methods, str) else tuple(methods)
        with self._lock:
            if version is None:
                have = [rec["version"]
                        for rec in self._published.get(name, ())]
                version = (max(have) + 1) if have else 1
            version = int(version)
        rec = {"name": name, "model": model, "methods": methods,
               "version": version, "serve_dtype": serve_dtype}
        # serialize against respawns: a replica respawning inside the
        # register->publish window would replay _published WITHOUT this
        # model yet re-enter rotation, and then serve KeyError — a
        # request-owned verdict failover will not absorb
        with self._respawn_lock:
            live = [r for r in self._replicas
                    if r.alive and not r.draining]
            if not live:
                raise AllReplicasUnhealthy(
                    "no live replica to roll out onto; wait for the "
                    "supervisor's respawns (or unpark)"
                )
            done = []
            try:
                for r in live:
                    self._register_on(r, rec)
                    done.append(r)
            except Exception:
                # roll the orphans back: a version registered on SOME
                # replicas but never published would make every retry
                # of this rollout fail "already registered" (versions
                # are immutable worker-side). Best-effort — a replica
                # that dies mid-rollback respawns consistent from
                # _published anyway.
                for r in done:
                    try:
                        r.pool.request(
                            "unregister",
                            {"name": name, "version": version},
                            self.heartbeat_timeout_s * 4,
                        )
                    except Exception as exc:
                        faults.log_suppressed(
                            "ProcessReplicaSet.rollout.rollback", exc
                        )
                raise
            with self._lock:
                self._published.setdefault(name, []).append(rec)
                # a fleet-wide rollout puts the name on EVERY replica,
                # so any earlier shard restriction no longer applies
                self._shard_of.pop(name, None)
        self._event("rollout", None, name=name, version=version,
                    serve_dtype=serve_dtype)
        return version

    register = rollout

    def rollout_many(self, models, methods=("predict",),
                     serve_dtype="float32", n_shards=None,
                     replication=1):
        """Bulk catalog rollout with bank-aware sharding, the
        cross-process mirror of ``ReplicaSet.rollout_many``: the
        PARENT assigns version numbers and the tenant→shard→holders
        map (stable-hash shards, rendezvous-hashed holders), and each
        holder WORKER stages its whole subset behind one bank
        generation per bank group (the ``register_many`` worker op —
        one RPC carrying the cohort, not one per tenant). Routing for
        sharded models restricts to holders; a shard whose holders are
        all down is re-staged on another live worker
        (:meth:`_restage_shard`) while the supervisor respawns the
        holders with their original subsets. ``n_shards=None``
        defaults to one shard per live replica; ``n_shards=1``
        degenerates to replicate-everywhere bulk load. Returns the
        fleet-assigned versions in input order; a worker failing
        mid-rollout rolls back the staged workers and raises without
        publishing."""
        if self._closed:
            raise ServingError("replica set is closed")
        items = list(models.items()) if isinstance(models, dict) \
            else list(models)
        if not items:
            return []
        methods = (methods,) if isinstance(methods, str) \
            else tuple(methods)
        with self._respawn_lock:
            live = [r for r in self._replicas
                    if r.alive and not r.draining]
            if not live:
                raise AllReplicasUnhealthy(
                    "no live replica to roll out onto; wait for the "
                    "supervisor's respawns (or unpark)"
                )
            if n_shards is None:
                n_shards = len(live)
            n_shards = max(1, int(n_shards))
            replication = max(1, min(int(replication), len(live)))
            with self._lock:
                nxt = {}
                vers = []
                for name, _ in items:
                    base = nxt.get(name)
                    if base is None:
                        prior = [rec["version"]
                                 for rec in self._published.get(name, ())]
                        base = max(prior) + 1 if prior else 1
                    vers.append(base)
                    nxt[name] = base + 1
            if n_shards <= 1:
                shard_of = None
                holders = {}
                per_replica = {
                    r.index: (list(items), list(vers)) for r in live
                }
            else:
                shard_of = {name: _stable_hash(name) % n_shards
                            for name, _ in items}
                live_idx = [r.index for r in live]
                holders = {
                    s: _rendezvous_holders(s, live_idx, replication)
                    for s in set(shard_of.values())
                }
                per_replica = {}
                for (name, model), v in zip(items, vers):
                    for ri in holders[shard_of[name]]:
                        sub, sv = per_replica.setdefault(ri, ([], []))
                        sub.append((name, model))
                        sv.append(v)
            by_index = {r.index: r for r in live}
            done = []
            try:
                with obs_trace.span(
                    "rollout_swap",
                    {"models": len(items), "shards": int(n_shards),
                     "replication": int(replication)}
                    if obs_trace.enabled() else None,
                ):
                    for ri in sorted(per_replica):
                        sub, sv = per_replica[ri]
                        self._register_many_on(
                            by_index[ri], sub, sv, methods, serve_dtype
                        )
                        done.append((by_index[ri], sub, sv))
            except Exception:
                # roll the orphans back (same reasoning as rollout():
                # versions are immutable worker-side, so an orphaned
                # registration would poison every retry)
                for r, sub, sv in done:
                    for (name, _), v in zip(sub, sv):
                        try:
                            r.pool.request(
                                "unregister",
                                {"name": name, "version": v},
                                self.heartbeat_timeout_s * 4,
                            )
                        except Exception as exc:
                            faults.log_suppressed(
                                "ProcessReplicaSet.rollout_many.rollback",
                                exc,
                            )
                raise
            with self._lock:
                for (name, model), v in zip(items, vers):
                    rec = {"name": name, "model": model,
                           "methods": methods, "version": v,
                           "serve_dtype": serve_dtype}
                    if shard_of is not None:
                        rec["shard"] = shard_of[name]
                        self._shard_of[name] = shard_of[name]
                    else:
                        self._shard_of.pop(name, None)
                    self._published.setdefault(name, []).append(rec)
                for s, hs in holders.items():
                    self._shard_holders[s] = list(hs)
                if shard_of is not None:
                    self._n_shards = max(self._n_shards, n_shards)
        self._event("rollout_many", None, n=len(items),
                    n_shards=int(n_shards), replication=int(replication))
        return vers

    def unregister(self, name, version=None):
        """Fleet-wide unload: drop ``name@version`` (every version with
        ``version=None``) from every routable worker AND from the
        rollout spec store, so respawned generations do not re-register
        it. On banked workers this shrinks each worker's bank in place
        (compaction releases the stacked device bytes) while the other
        tenants keep serving. Returns the per-replica removed-spec
        lists."""
        if self._closed:
            raise ServingError("replica set is closed")
        # a sharded model lives only on its holders; unload there
        _, holders = self._route_for(name)
        with self._respawn_lock:
            live = [r for r in self._replicas
                    if r.alive and not r.draining
                    and (holders is None or r.index in holders)]
            removed = []
            for r in live:
                try:
                    out = r.pool.request(
                        "unregister",
                        {"name": name, "version": version},
                        self.heartbeat_timeout_s * 4,
                    )
                    removed.append(out.get("removed", []))
                except Exception as exc:
                    # a replica that cannot answer respawns consistent
                    # from the (about to be updated) _published store
                    faults.log_suppressed(
                        "ProcessReplicaSet.unregister", exc
                    )
            with self._lock:
                recs = self._published.get(name)
                if recs is not None:
                    if version is None:
                        del self._published[name]
                    else:
                        recs[:] = [rec for rec in recs
                                   if rec["version"] != int(version)]
                        if not recs:
                            del self._published[name]
                if name not in self._published:
                    self._shard_of.pop(name, None)
        self._event("unregister", None, name=name, version=version)
        return removed

    def _register_on(self, r, rec):
        # registration compiles (or loads AOT artifacts) — give it the
        # spawn budget, not the request budget
        return r.pool.request("register", dict(rec), self.spawn_timeout_s)

    def _register_many_on(self, r, items, versions, methods,
                          serve_dtype):
        """One bulk ``register_many`` RPC: the worker stages the whole
        subset behind one bank generation per bank group. The budget
        scales past the single-spawn budget — a 10k-tenant cohort is
        one pickle + one staging, but not a 60-second one."""
        return r.pool.request(
            "register_many",
            {"models": list(items), "versions": list(versions),
             "methods": tuple(methods), "serve_dtype": serve_dtype},
            max(self.spawn_timeout_s * 4, 120.0),
        )

    def _route_for(self, model):
        """``(shard, holder-index set)`` for a sharded model;
        ``(None, None)`` for replicate-everywhere routing."""
        if model is None:
            return None, None
        name = str(model).split("@", 1)[0]
        with self._lock:
            s = self._shard_of.get(name)
            if s is None:
                return None, None
            return s, set(self._shard_holders.get(s, ()))

    def _records_for_replica(self, index):
        """The published records worker ``index`` must hold: every
        unsharded record plus the shards the holder map assigns it."""
        with self._lock:
            return [
                dict(rec)
                for recs in self._published.values() for rec in recs
                if rec.get("shard") is None
                or index in self._shard_holders.get(rec["shard"], ())
            ]

    def _replay_records(self, r, recs):
        """Re-register ``recs`` on worker ``r``, bulk per
        (methods, serve_dtype) group with versions pinned — a respawn
        or re-stage costs one bank generation per group."""
        groups = {}
        for rec in recs:
            k = (tuple(rec["methods"]),
                 rec.get("serve_dtype", "float32"))
            groups.setdefault(k, []).append(rec)
        for (methods, sdt), grp in groups.items():
            if len(grp) == 1:
                self._register_on(r, grp[0])
            else:
                self._register_many_on(
                    r, [(g["name"], g["model"]) for g in grp],
                    [g["version"] for g in grp], methods, sdt,
                )

    def _restage_shard(self, shard, exclude):
        """Failover past every holder of ``shard``: re-stage the
        shard's ENTIRE record set on another live worker (one bulk
        staging), republish the holder map, return the new holder —
        or ``None`` when no live worker remains."""
        with self._lock:
            names = [n for n, s in self._shard_of.items() if s == shard]
            recs = [dict(rec) for n in names
                    for rec in self._published.get(n, ())]
            cands = sorted(
                (r for r in self._replicas
                 if r.alive and not r.draining
                 and r.index not in exclude),
                key=lambda r: r.in_flight + r.queue_depth,
            )
        if not recs:
            return None
        for r in cands:
            try:
                self._replay_records(r, recs)
            except Exception as exc:
                faults.log_suppressed(
                    "ProcessReplicaSet._restage_shard", exc
                )
                continue
            with self._lock:
                hold = self._shard_holders.setdefault(shard, [])
                if r.index not in hold:
                    hold.append(r.index)
            faults.record("shard_restages")
            obs_trace.instant(
                "shard_restage",
                {"shard": int(shard), "replica": int(r.index),
                 "models": len(recs)}
                if obs_trace.enabled() else None,
            )
            self._event("restage", r.index, shard=shard,
                        models=len(recs))
            return r
        return None

    # ------------------------------------------------------------------
    # request path
    # ------------------------------------------------------------------
    def submit(self, X, model=None, method="predict", timeout_s=None):
        """Route one request; returns a Future (resolved on a fleet
        dispatch thread). Failover semantics mirror ``ReplicaSet``."""
        if self._closed:
            raise ServingError("replica set is closed")
        self._tick()
        return self._executor.submit(
            self._routed_request, X, model, method, timeout_s
        )

    def predict(self, X, model=None, method="predict", timeout_s=None):
        fut = self.submit(X, model=model, method=method,
                          timeout_s=timeout_s)
        wait = None if timeout_s is None else timeout_s + max(
            2.0, 2 * len(self._replicas) * 0.5
        )
        try:
            return fut.result(timeout=wait)
        except _FutureTimeout:
            raise DeadlineExceeded(
                f"no result within {timeout_s}s (+fleet grace)"
            ) from None

    def predict_proba(self, X, model=None, timeout_s=None):
        return self.predict(X, model=model, method="predict_proba",
                            timeout_s=timeout_s)

    def decision_function(self, X, model=None, timeout_s=None):
        return self.predict(X, model=model, method="decision_function",
                            timeout_s=timeout_s)

    def _routed_request(self, X, model, method, timeout_s):
        tried = set()
        last = None
        # bank-aware routing: a sharded model routes only to holders
        shard, holders = self._route_for(model)
        give_up_at = time.monotonic() + self.unhealthy_wait_s
        while True:
            r = self._pick(tried, allowed=holders)
            if r is None and holders is not None:
                # every holder down/refused: re-stage the shard on
                # another live worker rather than waiting out the
                # supervisor's respawn backoff
                restaged = self._restage_shard(shard, tried | holders)
                if restaged is not None:
                    holders.add(restaged.index)
                    continue
            if r is None:
                with self._lock:
                    all_parked = all(p.parked for p in self._replicas)
                if all_parked or time.monotonic() >= give_up_at:
                    obs_flightrec.recorder().dump_incident(
                        "all_replicas_unhealthy", dir=self.incident_dir,
                    )
                    exc = AllReplicasUnhealthy(
                        f"all {len(self._replicas)} replica processes "
                        "refused the request"
                        + (" (whole fleet parked after crash loops)"
                           if all_parked else "")
                    )
                    exc.__cause__ = last
                    raise exc
                # replicas are down but respawns are pending: wait a
                # beat for the supervisor rather than failing a request
                # into a healing fleet
                time.sleep(min(0.1, self.heartbeat_interval_s))
                tried.clear()
                continue
            tried.add(r.index)
            rpc_timeout = (self.request_timeout_s if timeout_s is None
                           else timeout_s + max(2.0, self.heartbeat_timeout_s))
            with self._lock:
                r.routed += 1
                r.in_flight += 1
            try:
                # the routing span is the fleet trace's cross-process
                # parent: the request frame ships the context, the
                # worker adopts it, and its flush/compile spans parent
                # here in the stitched Perfetto view
                traced = obs_trace.enabled()
                payload = {"X": X, "model": model, "method": method,
                           "timeout_s": timeout_s}
                with obs_trace.use_context(
                    obs_trace.new_context() if traced else None
                ), obs_trace.span(
                    "route",
                    {"replica": int(r.index), "method": str(method)}
                    if traced else None,
                ):
                    if traced:
                        payload["_trace"] = obs_trace.current_context()
                    out = self._request_on(r, payload, rpc_timeout)
                with self._lock:
                    r.failures = 0
                return out
            except Exception as exc:
                last = exc
                if not self._failover_worthy(r, exc):
                    raise
            finally:
                with self._lock:
                    r.in_flight -= 1

    def _request_on(self, r, payload, rpc_timeout):
        """One ``request`` RPC on one replica, riding the shm data
        plane when it can (module docstring: the socket is then only
        the doorbell). The fallback matrix is counted, never an error:

        ======================  =======================================
        condition               payload rides
        ======================  =======================================
        ring attached + fits    shm slot (descriptor on the doorbell)
        ring full               pickled frame (+``serve.shm_fallbacks``)
        payload > slot_bytes    pickled frame (+fallback counter)
        non-numeric payload     pickled frame
        no ring / SKDIST_SHM=0  pickled frame
        reply too big for slot  shm out, pickled reply (+fallback)
        ======================  =======================================

        Transport overhead — serialize/send + reply read/decode + the
        two ring memcpys — is accumulated per plane in
        ``self._transport`` (the wirespeed smoke's >=5x gate)."""
        ring = r.ring
        X = payload.get("X")
        slot = None
        used_shm = False
        shm_s = 0.0
        if (ring is not None and isinstance(X, np.ndarray)
                and X.dtype.kind in "fiub" and not X.dtype.hasobject):
            if ring.fits(X.nbytes):
                slot = ring.acquire()
                if slot is None:
                    # ring full: more in-flight requests than slots —
                    # counted, and this one rides the classic frame
                    _transport_counter("serve.shm_fallbacks").inc()
            else:
                # oversized payload: routed around the ring, counted
                _transport_counter("serve.shm_fallbacks").inc()
        try:
            if slot is not None:
                t0 = time.perf_counter()
                desc = ring.write(slot, X)
                shm_s += time.perf_counter() - t0
                payload = {k: v for k, v in payload.items() if k != "X"}
                payload["shm"] = desc
                used_shm = True
            reply, wire_s = r.pool.request_raw(
                "request", payload, rpc_timeout
            )
            if not reply.get("ok"):
                raise decode_error(reply)
            out_desc = reply.get("shm")
            if out_desc is not None:
                if slot is None:
                    raise ReplicaConnectionError(
                        "replica sent an shm reply to a pickled request"
                    )
                t0 = time.perf_counter()
                out = ring.read(out_desc)
                shm_s += time.perf_counter() - t0
                _transport_counter("serve.shm_bytes").inc(
                    int(X.nbytes) + int(out.nbytes)
                )
            else:
                out = reply.get("value")
                _transport_counter("serve.frames_pickled").inc()
                if used_shm:
                    # rows went over the ring but the reply came back
                    # pickled (result outgrew the slot / non-numeric)
                    _transport_counter("serve.shm_fallbacks").inc()
            plane = "shm" if (used_shm and out_desc is not None) \
                else "pickle"
            with self._lock:
                ent = self._transport[plane]
                ent[0] += 1
                ent[1] += wire_s + shm_s
            return out
        finally:
            if slot is not None:
                ring.release(slot)
            if ring is not None:
                obs_metrics.registry().gauge(
                    "serve.shm_ring_occupancy",
                    help="claimed ring slots per replica at the last "
                         "routed request",
                ).set(ring.occupancy(), replica=str(r.index))

    def _pick(self, exclude=(), allowed=None):
        """Least-loaded live replica not yet tried (restricted to
        ``allowed`` holder indices for sharded models): parent-side
        in-flight plus the child's queue depth from its last
        heartbeat, ties round-robin."""
        with self._lock:
            live = [r for r in self._replicas
                    if r.alive and not r.draining
                    and r.index not in exclude
                    and (allowed is None or r.index in allowed)]
            self._rr += 1
            rr = self._rr
            if not live:
                return None
            return min(
                live,
                key=lambda r: (r.in_flight + r.queue_depth,
                               (r.index - rr) % len(self._replicas)),
            )

    def _failover_worthy(self, r, exc):
        """Mirror of ``ReplicaSet._failover_worthy`` across the process
        boundary: request-owned verdicts surface; transport deaths
        declare the process dead immediately; everything else strikes
        toward a supervised restart."""
        if isinstance(exc, (ValueError, TypeError, KeyError,
                            DeadlineExceeded)):
            return False
        faults.record("replica_failovers")
        obs_trace.instant(
            "replica_failover",
            {"replica": int(r.index), "error": type(exc).__name__}
            if obs_trace.enabled() else None,
        )
        if isinstance(exc, Overloaded):
            return True  # load, not sickness: re-route without a strike
        if isinstance(exc, ReplicaConnectionError):
            self._declare_dead(r, f"connection: {exc}")
            return True
        with self._lock:
            r.failures += 1
            sick = (
                isinstance(exc, (CircuitOpen, faults.WatchdogTimeout))
                or r.failures >= self.sick_threshold
            )
        if sick:
            self._declare_dead(r, f"sick: {type(exc).__name__}")
        return True

    # ------------------------------------------------------------------
    # supervisor
    # ------------------------------------------------------------------
    def _supervise(self):
        while not self._closed:
            self._stop_evt.wait(self.heartbeat_interval_s)
            if self._closed:
                return
            for r in list(self._replicas):
                if self._closed:
                    return
                try:
                    self._supervise_one(r)
                except Exception as exc:
                    # the supervisor thread is the fleet's liveness —
                    # a surprise from one replica's bookkeeping must
                    # not kill heartbeats for every other replica
                    faults.log_suppressed(
                        "ProcessReplicaSet._supervise", exc
                    )

    def _harvest_loop(self):
        """The periodic telemetry harvest runs on its OWN thread: one
        wedged replica can hold a harvest RPC for its full timeout,
        and that stall must never delay heartbeat-miss accrual or
        respawns for the rest of the fleet (the supervisor thread IS
        the fleet's liveness)."""
        while not self._closed:
            self._stop_evt.wait(self.harvest_interval_s)
            if self._closed:
                return
            if harvest_enabled():
                try:
                    self.harvest_now()
                except Exception as exc:
                    faults.log_suppressed(
                        "ProcessReplicaSet._harvest_loop", exc
                    )

    def _supervise_one(self, r):
        if r.parked:
            return
        if not r.alive:
            due = r.respawn_due_at
            if due is not None and time.monotonic() >= due:
                with self._lock:
                    r.respawn_due_at = None  # one submission per due
                self._respawn_exec.submit(self._respawn, r)
            return
        if r.proc is not None and r.proc.poll() is not None:
            self._declare_dead(
                r, f"exited rc={r.proc.returncode}", kill=False
            )
            return
        try:
            pong = r.pool.request(
                "ping", {}, self.heartbeat_timeout_s
            )
            r.misses = 0
            r.queue_depth = int(pong.get("queue_depth", 0))
            if pong.get("draining") and not r.draining:
                # external SIGTERM: route away now; the exit
                # lands in the poll() branch and respawns
                r.draining = True
                self._event("draining", r.index)
        except Exception:
            r.misses += 1
            faults.record("heartbeat_misses")
            obs_trace.instant(
                "replica_heartbeat_miss",
                {"replica": int(r.index), "misses": int(r.misses)}
                if obs_trace.enabled() else None,
            )
            if r.misses >= self.miss_threshold:
                self._declare_dead(
                    r, f"heartbeat: {r.misses} consecutive misses"
                )

    def _declare_dead(self, r, reason, kill=True):
        """Take a replica out of rotation NOW: SIGKILL its process
        group (unless it already exited) and schedule a respawn."""
        with self._lock:
            if not r.alive:
                return
            r.alive = False
        if kill and r.proc is not None:
            _kill_group(r.proc)
        if r.proc is not None:
            try:
                r.proc.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                pass  # unkillable: abandoned, never inherited as a hang
        if r.pool is not None:
            r.pool.close()
        self._event("dead", r.index, reason=reason,
                    generation=r.generation)
        self._record_death(r, reason)

    def _record_death(self, r, reason):
        """Crash-loop accounting + respawn scheduling (also the landing
        path for failed spawns)."""
        now = time.monotonic()
        # the ring dies with its generation, HERE in the supervisor:
        # the worker may have been SIGKILLed mid-ring-write and can
        # free nothing. Occupancy is read first — the incident file
        # records how many slots were claimed at the moment of death.
        ring_occ = None
        if r.ring is not None:
            ring_occ = r.ring.occupancy()
            r.ring.close()
            r.ring = None
        with self._lock:
            r.alive = False
            r.draining = False
            r.death_reason = reason
            if r.intentional_stop:
                # operator-driven drain/stop: not a crash, no backoff
                r.intentional_stop = False
                r.respawn_due_at = None
                return
            r.deaths.append(now)
            while r.deaths and now - r.deaths[0] > self.crash_loop_window_s:
                r.deaths.popleft()
            r.consecutive_deaths += 1
            if len(r.deaths) >= self.crash_loop_threshold:
                r.parked = True
                r.respawn_due_at = None
            else:
                backoff = min(
                    self.respawn_backoff_s
                    * (2.0 ** (r.consecutive_deaths - 1)),
                    self.max_respawn_backoff_s,
                )
                r.respawn_due_at = now + backoff
        if r.parked:
            faults.record("crash_loop_parks")
            self._event(
                "parked", r.index, reason=reason,
                deaths_in_window=len(r.deaths),
            )
        # the post-mortem: a timestamped incident file combining the
        # supervisor's flight recorder with the dead child's LAST
        # standing snapshot (written by its autodump thread — the only
        # telemetry a SIGKILLed process leaves behind)
        self._dump_replica_incident(
            r, "crash_loop_park" if r.parked else "replica_death", reason,
            ring_occupancy=ring_occ,
        )

    def _dump_replica_incident(self, r, kind, reason,
                               ring_occupancy=None):
        worker_snap = None
        try:
            if r.flightrec_path and os.path.exists(r.flightrec_path):
                with open(r.flightrec_path, "r", encoding="utf-8") as fh:
                    worker_snap = json.load(fh)
        except Exception as exc:
            faults.log_suppressed(
                "ProcessReplicaSet._dump_replica_incident", exc
            )
            worker_snap = {"error": repr(exc)}
        path = obs_flightrec.recorder().dump_incident(
            f"{kind}-replica{r.index}", dir=self.incident_dir,
            extra={
                "replica": int(r.index),
                "generation": int(r.generation),
                "pid": r.pid,
                "death_reason": str(reason),
                "worker_flightrec": worker_snap,
                # claimed shm slots at the moment of death: >0 means
                # the worker died with requests in flight over the ring
                "ring_occupancy": ring_occupancy,
            },
        )
        if path is not None:
            self._event("incident", r.index, path=path,
                        incident_kind=kind)
        return path

    def _respawn(self, r, reason=None):
        """Respawn one dead replica: fresh process, wait ready,
        re-register every published model under its original version,
        return it to rotation."""
        with self._respawn_lock:
            if r.alive or r.parked or self._closed:
                return False
            reason = reason or r.death_reason
            with self._lock:
                # an explicit revive attempt ends any "intentional
                # stop" era NOW: if THIS spawn fails, that failure is
                # a real death (backoff + crash-loop accounting), not
                # a stop to be shrugged off
                r.intentional_stop = False
            with obs_trace.span(
                "replica_respawn",
                {"replica": int(r.index), "pid": r.pid,
                 "reason": str(reason)}
                if obs_trace.enabled() else None,
            ):
                old_pool = r.pool
                if old_pool is not None:
                    old_pool.close()
                try:
                    self._spawn(r)
                    # replay what THIS worker holds: unsharded records
                    # plus its shard-map subset, bulk-staged (one bank
                    # generation per group, versions pinned)
                    self._replay_records(
                        r, self._records_for_replica(r.index)
                    )
                except Exception as exc:
                    # ANY failure — spawn OSError, a decoded
                    # registration ValueError, transport death — is a
                    # failed respawn feeding the crash-loop accounting,
                    # never an escape that kills the supervisor thread
                    if r.proc is not None:
                        _kill_group(r.proc)
                    self._record_death(r, f"respawn: {exc}")
                    return False
                with self._lock:
                    r.failures = 0
                    r.misses = 0
                    r.queue_depth = 0
                    r.consecutive_deaths = 0
                    r.respawn_due_at = None
                    r.alive = True
        faults.record("replica_proc_restarts")
        self._event("respawn", r.index, generation=r.generation,
                    pid=r.pid, reason=str(reason))
        return True

    def heal(self):
        """Respawn every dead (non-parked) replica NOW, ignoring
        backoff — deterministic tests and drain-then-upgrade ops."""
        n = 0
        for r in self._replicas:
            if not r.alive and not r.parked:
                if self._respawn(r, reason="heal"):
                    n += 1
        return n

    def unpark(self, index):
        """Clear a parked replica's crash-loop verdict and respawn it
        (operator API — after fixing whatever crashed the worker)."""
        r = self._replicas[int(index)]
        with self._lock:
            r.parked = False
            r.deaths.clear()
            r.consecutive_deaths = 0
        self._event("unpark", r.index)
        return self._respawn(r, reason="unpark")

    # ------------------------------------------------------------------
    # lifecycle ops
    # ------------------------------------------------------------------
    def kill_replica(self, index, sig=signal.SIGKILL):
        """Send ``sig`` to replica ``index``'s process group NOW —
        abrupt death (the supervisor's poll/heartbeat notices and
        respawns). Operational API and the target of
        ``FaultInjector.kill_replica_proc``."""
        r = self._replicas[int(index)]
        self._event("kill", r.index, sig=int(sig))
        if r.proc is not None:
            _kill_group(r.proc, sig)
        return r

    def stall_replica(self, index, resume_after_s=None):
        """SIGSTOP replica ``index``'s process group — the
        heartbeat-stall scenario: the process is alive but
        unresponsive, which the supervisor must treat as death.
        ``resume_after_s`` schedules a SIGCONT (a stopped process dies
        to the supervisor's SIGKILL either way)."""
        r = self._replicas[int(index)]
        self._event("stall", r.index, resume_after_s=resume_after_s)
        if r.proc is not None:
            _kill_group(r.proc, signal.SIGSTOP)
            if resume_after_s is not None:
                proc = r.proc
                timer = threading.Timer(
                    float(resume_after_s),
                    lambda: _kill_group(proc, signal.SIGCONT),
                )
                timer.daemon = True
                timer.start()
        return r

    def stop_replica(self, index, drain=True, timeout=None):
        """Graceful stop: SIGTERM (the worker drains and exits 0);
        SIGKILL the group only past ``timeout`` (default
        ``drain_timeout_s``). The stop is intentional — no crash-loop
        strike, no automatic respawn."""
        r = self._replicas[int(index)]
        if timeout is None:
            timeout = self.drain_timeout_s
        with self._lock:
            r.intentional_stop = True
            r.alive = False
            r.draining = False
        self._event("stop", r.index, drain=bool(drain))
        proc = r.proc
        if proc is not None and proc.poll() is None:
            _kill_group(proc, signal.SIGTERM if drain else signal.SIGKILL)
            try:
                proc.wait(timeout=timeout if drain else 5.0)
            except subprocess.TimeoutExpired:
                _kill_group(proc)
                try:
                    proc.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    pass  # unkillable: abandon (childproc contract)
        if r.pool is not None:
            r.pool.close()
        if r.ring is not None:
            r.ring.close()  # owner close: unmap + unlink /dev/shm
            r.ring = None
        return r

    def rolling_restart(self):
        """Drain + respawn one replica at a time: the fleet serves
        throughout, every replica comes back a fresh process (fresh
        generation) fully re-registered — zero-downtime worker
        upgrade. Parked replicas are skipped. Returns the number
        restarted."""
        n = 0
        for r in self._replicas:
            if r.parked:
                continue
            self.stop_replica(r.index, drain=True)
            if self._respawn(r, reason="rolling_restart"):
                n += 1
        self._event("rolling_restart", None, restarted=n)
        return n

    def close(self, drain=True, timeout=None):
        """Stop the supervisor, gracefully stop every worker (SIGTERM
        drain by default; SIGKILL past ``drain_timeout_s``)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._stop_evt.set()
        self._supervisor.join(timeout=5.0)
        if self._harvester is not None:
            self._harvester.join(timeout=5.0)
        if self._obs_server is not None:
            try:
                self._obs_server.stop()
            except Exception as exc:
                faults.log_suppressed("ProcessReplicaSet.close.obs", exc)
        for r in self._replicas:
            if r.proc is not None:
                try:
                    self.stop_replica(r.index, drain=drain,
                                      timeout=timeout)
                except Exception as exc:
                    faults.log_suppressed("ProcessReplicaSet.close", exc)
        for r in self._replicas:
            # belt and braces: any ring the per-replica stop paths
            # missed (never-spawned replica, racing death) unlinks here
            if r.ring is not None:
                r.ring.close()
                r.ring = None
        self._executor.shutdown(wait=False)
        self._respawn_exec.shutdown(wait=False)
        import shutil

        shutil.rmtree(self._dir, ignore_errors=True)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # ------------------------------------------------------------------
    # telemetry harvest (cross-process observability)
    # ------------------------------------------------------------------
    def _harvest_one(self, r):
        """Pull one replica's telemetry frame. ANY failure — the
        worker died mid-RPC, answers an older frame schema, is parked
        or between generations — keeps the replica's LAST harvested
        state and marks it stale; harvest never throws past here."""
        if not r.alive or r.draining or r.pool is None:
            r.telemetry_stale = True
            return False
        try:
            reply = r.pool.request(
                "telemetry", {"schema": TELEMETRY_SCHEMA},
                self.heartbeat_timeout_s * 4,
            )
            if (not isinstance(reply, dict)
                    or reply.get("schema") != TELEMETRY_SCHEMA
                    or not isinstance(reply.get("state"), dict)):
                raise ServingError(
                    "telemetry schema mismatch: got "
                    f"{reply.get('schema') if isinstance(reply, dict) else type(reply).__name__!r}, "
                    f"want {TELEMETRY_SCHEMA} (mixed-version fleet?)"
                )
        except Exception as exc:
            r.telemetry_stale = True
            faults.log_suppressed("ProcessReplicaSet.harvest", exc)
            return False
        r.telemetry_state = reply["state"]
        r.telemetry_pid = reply.get("pid")
        r.telemetry_compiles = reply.get("compiles_after_warmup")
        if reply.get("trace") is not None:
            r.trace_part = reply["trace"]
        r.flightrec_events = reply.get("flightrec")
        r.telemetry_stale = False
        return True

    def harvest_now(self):
        """Harvest every routable replica synchronously; returns the
        number of fresh harvests. The supervisor calls this on its
        ``harvest_interval_s`` cadence; scrapes, :meth:`stats` and the
        trace export call it on demand."""
        return sum(self._harvest_one(r) for r in list(self._replicas))

    def fleet_registry(self, refresh=False):
        """ONE registry covering the whole fleet: the supervisor's own
        families merged with every replica's last harvested dump,
        labeled ``replica``/``pid`` — the Prometheus-federation shape.
        The ``stale`` gauge (exposed as ``skdist_stale{replica=...}``)
        marks replicas whose last harvest failed: their numbers are
        present but frozen at the last good harvest."""
        if refresh:
            self.harvest_now()
        reg = obs_metrics.MetricsRegistry()
        obs_metrics.merge_state(
            obs_metrics.registry().dump_state(), reg
        )
        stale = reg.gauge(
            "stale",
            help="1 when the replica's last telemetry harvest failed "
                 "(its merged numbers are frozen at the last success)",
        )
        with self._lock:
            replicas = list(self._replicas)
        for r in replicas:
            labels = {"replica": r.index}
            if r.telemetry_pid is not None:
                labels["pid"] = r.telemetry_pid
            if r.telemetry_state is not None:
                try:
                    obs_metrics.merge_state(r.telemetry_state, reg, labels)
                except Exception as exc:
                    # a malformed dump degrades THIS replica to stale,
                    # never the whole exposition
                    r.telemetry_stale = True
                    faults.log_suppressed(
                        "ProcessReplicaSet.fleet_registry", exc
                    )
            stale.set(
                1 if (r.telemetry_stale or r.telemetry_state is None)
                else 0,
                replica=str(r.index),
            )
        return reg

    def fleet_metrics_text(self, refresh=False):
        """Prometheus exposition of :meth:`fleet_registry` — what the
        ops endpoint's ``/metrics`` serves."""
        return obs_export.prometheus_text(self.fleet_registry(refresh))

    def fleet_json_snapshot(self, refresh=False, path=None):
        """JSON counterpart of :meth:`fleet_metrics_text`."""
        return obs_export.json_snapshot(
            self.fleet_registry(refresh), path=path
        )

    def export_fleet_trace(self, path=None, refresh=True):
        """Stitch the router's trace ring with every replica's
        harvested ring into one Perfetto-loadable Chrome trace: one
        named track per process, worker flush/compile spans
        parent-linked (flow arrows) under the router's ``route``
        spans. Dead replicas contribute their last harvested ring."""
        if refresh:
            self.harvest_now()
        parts = [obs_trace.trace_part(
            label=f"router (pid {os.getpid()})"
        )]
        for r in list(self._replicas):
            part = r.trace_part
            if not part:
                continue
            part = dict(part)
            part["label"] = f"replica {r.index} (pid {part.get('pid')})"
            parts.append(part)
        return obs_trace.stitch_traces(parts, path=path)

    def _healthz(self):
        """The ops endpoint's liveness doc: healthy while ANY replica
        is routable (the router's own availability criterion)."""
        with self._lock:
            replicas = [{
                "index": r.index, "alive": r.alive, "parked": r.parked,
                "draining": r.draining, "generation": r.generation,
                "pid": r.pid, "stale": r.telemetry_stale,
            } for r in self._replicas]
            requests = self._requests
        live = sum(1 for r in replicas
                   if r["alive"] and not r["draining"])
        return {
            "healthy": bool(live) and not self._closed,
            "live_replicas": live,
            "n_replicas": len(replicas),
            "requests": requests,
            "replicas": replicas,
        }

    def _flightrec_doc(self):
        """The ops endpoint's ``/debug/flightrec``: the supervisor's
        own recorder plus every replica's last harvested ring."""
        return {
            "router": obs_flightrec.recorder().snapshot_doc(),
            "replicas": {
                str(r.index): r.flightrec_events
                for r in list(self._replicas)
            },
        }

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------
    def stats(self):
        """Fleet snapshot, schema-matched to ``ReplicaSet.stats()``:
        router gauges, per-replica entries with the child engine's own
        stats (fetched over the wire), and the fleet ``by_model``
        rollup — plus the supervisor's process-level view (pid,
        parked, queue depth) and the harvested telemetry block.
        Refreshes the harvest first (this is an operator call already
        paying one RPC per replica; the ``SKDIST_OBS_HARVEST=0``
        switch gates only the PERIODIC harvest, per its docstring)."""
        if not self._closed:
            self.harvest_now()
        with self._lock:
            replicas = list(self._replicas)
            out = {
                "n_replicas": len(replicas),
                "requests": self._requests,
                "published": sorted(self._published),
                "pending_respawn": [r.index for r in replicas
                                    if not r.alive and not r.parked],
                "parked": [r.index for r in replicas if r.parked],
                "events": [dict(e) for e in self.events],
                "n_shards": self._n_shards,
                "sharded_models": len(self._shard_of),
                "shard_holders": {
                    int(s): list(h)
                    for s, h in self._shard_holders.items()
                },
            }
        per = []
        for r in replicas:
            ent = {
                "index": r.index, "alive": r.alive,
                "generation": r.generation, "routed": r.routed,
                "pid": r.pid, "parked": r.parked,
                "queue_depth": r.queue_depth,
            }
            ent["engine"] = None
            if r.alive and r.pool is not None:
                try:
                    ent["engine"] = r.pool.request(
                        "stats", {}, self.heartbeat_timeout_s * 4
                    )
                except Exception as exc:
                    faults.log_suppressed("ProcessReplicaSet.stats", exc)
            per.append(ent)
        out["replicas"] = per
        out["by_model"] = fleet_by_model(per)
        # the harvested view (satellite of the cross-process harvest):
        # per-replica scoped compile deltas as the SUPERVISOR merged
        # them — the 0-compile gates read these instead of trusting a
        # field each worker computed about itself mid-frame
        out["harvest"] = {
            "enabled": harvest_enabled(),
            "replicas": {
                str(r.index): {
                    "stale": bool(r.telemetry_stale
                                  or r.telemetry_state is None),
                    "pid": r.telemetry_pid,
                    "compiles_after_warmup": r.telemetry_compiles,
                }
                for r in replicas
            },
        }
        with self._lock:
            tr = {k: list(v) for k, v in self._transport.items()}
        out["transport"] = {
            "enabled": self.shm_slots > 0 and shm_enabled(),
            "shm_requests": tr["shm"][0],
            "pickle_requests": tr["pickle"][0],
            "shm_mean_overhead_s": (tr["shm"][1] / tr["shm"][0]
                                    if tr["shm"][0] else None),
            "pickle_mean_overhead_s": (tr["pickle"][1] / tr["pickle"][0]
                                       if tr["pickle"][0] else None),
        }
        return out

    def autotune_now(self):
        """Fan one synchronous autotune pass (``serve.autotune``) to
        every routable replica; returns the per-replica results. The
        mid-load ladder swap the wirespeed smoke drives — each worker
        prewarms its candidate geometry before its atomic cutover, so
        in-flight traffic never sees a compile."""
        results = {}
        for r in list(self._replicas):
            if not r.alive or r.draining or r.pool is None:
                continue
            try:
                results[r.index] = r.pool.request(
                    "autotune", {}, self.spawn_timeout_s,
                )
            except Exception as exc:
                faults.log_suppressed("ProcessReplicaSet.autotune", exc)
                results[r.index] = {"error": repr(exc)}
        self._event("autotune", None,
                    swapped=sum(len(v.get("swapped", []))
                                for v in results.values()
                                if isinstance(v, dict)))
        return results

    def replica(self, index):
        return self._replicas[int(index)]

    @property
    def ops_url(self):
        """Base URL of the ops endpoint, or None when it is off."""
        return (None if self._obs_server is None
                else self._obs_server.url)

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------
    def _event(self, kind, index, **extra):
        with self._lock:
            self.events.append(
                dict(kind=kind, replica=index, t=time.time(), **extra)
            )
        # fleet lifecycle rides the flight recorder too: an incident
        # file's event ring shows the kills/respawns/parks leading up
        # to whatever died
        obs_flightrec.note(f"fleet.{kind}", replica=index, **extra)

    def _tick(self):
        """Per-request housekeeping: deterministic request ordinal +
        the injector's process-level plans (kills/stalls due at this
        ordinal fire BEFORE the request routes, mirroring
        ``ReplicaSet._tick``)."""
        with self._lock:
            ordinal = self._requests
            self._requests += 1
        inj = faults.active_injector()
        kills = getattr(inj, "replica_proc_kills_due", None)
        if callable(kills):
            for idx, sig in kills(ordinal):
                self.kill_replica(idx, sig=sig)
        stalls = getattr(inj, "replica_proc_stalls_due", None)
        if callable(stalls):
            for idx, resume_after_s in stalls(ordinal):
                self.stall_replica(idx, resume_after_s=resume_after_s)
        return ordinal


