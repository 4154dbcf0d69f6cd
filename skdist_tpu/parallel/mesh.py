"""
Mesh construction helpers: single-host, multi-host (DCN × ICI), and the
2D tasks × data layout the estimators use.

The reference's "cluster" was a Spark deployment reached through one
SparkContext. Here the cluster is a ``jax.sharding.Mesh``:

- single host: all local devices on one 'tasks' axis (optionally split
  with a 'data' axis for row-sharding big X);
- multi-host: ``jax.distributed.initialize`` (the driver's analogue of
  spark-submit) makes every host see the global device set; the same
  SPMD program then runs on each host with the mesh spanning hosts.
  Lay the 'data' axis along ICI (fast all-reduce of gram/gradient
  partials) and the 'tasks' axis across DCN (embarrassingly parallel —
  no cross-task traffic), which is exactly what
  ``create_hybrid_device_mesh`` produces.
"""

import itertools
import json
import logging
import os
import re
import time

import numpy as np

from . import faults
from ..obs import metrics as obs_metrics, trace as obs_trace

__all__ = [
    "initialize_cluster",
    "task_data_mesh",
    "multihost_task_mesh",
    "match_partition_rules",
    "STREAM_BLOCK_RULES",
    "ElasticMeshManager",
    "HeartbeatFileProbe",
    "KVStoreHeartbeatProbe",
    "MaintenanceEventProbe",
    "combine_probes",
]

logger = logging.getLogger("skdist_tpu.mesh")

#: per-process ordinal for elastic managers' registry gauge labels
_MESH_IDS = itertools.count()


def initialize_cluster(coordinator_address=None, num_processes=None,
                       process_id=None, **jax_kwargs):
    """Join this host to a multi-host JAX cluster (no-op if already
    initialised or single-host). Wrapper over jax.distributed.

    ``jax_kwargs`` pass through to ``jax.distributed.initialize`` —
    on ELASTIC fleets raise ``heartbeat_timeout_seconds`` well above
    the default: the coordination service's fail-fast otherwise ABORTS
    every surviving process ~100s after a peer dies, while the elastic
    layer's epoch agreement is the membership authority that actually
    handles the loss."""
    import jax

    if num_processes in (None, 0, 1):
        return
    # Multi-process collectives on the CPU backend need an explicit
    # cross-process transport (jax ships gloo but defaults to 'none',
    # and the first cross-process device_put then fails with
    # "Multiprocess computations aren't implemented on the CPU
    # backend"). Harmless on TPU/GPU: the knob only shapes CPU client
    # construction. Must run before the backend is instantiated.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        **jax_kwargs,
    )


def task_data_mesh(devices=None, data_axis_size=1):
    """2D mesh ('tasks', 'data') over the given (default: all) devices.

    ``data_axis_size`` devices cooperate on each fit (row-sharded X,
    psum'd reductions); the remaining factor fans tasks out.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    if data_axis_size < 1 or n % data_axis_size != 0:
        raise ValueError(
            f"data_axis_size={data_axis_size} must divide device count {n}"
        )
    arr = np.array(devices).reshape(n // data_axis_size, data_axis_size)
    return Mesh(arr, ("tasks", "data"))


def multihost_task_mesh(data_axis_size=None):
    """Global 2D mesh for multi-host runs: 'data' along each host's
    local devices (ICI), 'tasks' across hosts × leftover local factor
    (DCN). On a single-host process this deterministically degenerates
    to :func:`task_data_mesh`; in a genuine multi-host run any
    construction failure propagates loudly instead of silently falling
    back to a single-host mesh (which would wedge the SPMD program the
    moment other hosts enter the collective).

    ``data_axis_size`` may exceed the local device count when it is a
    multiple of it: the 'data' axis then SPANS processes (e.g. 4 hosts
    × 2 devices with ``data_axis_size=4`` → each fit's row sharding
    crosses 2 hosts). Per-fit reductions (gram/gradient psums) then
    ride DCN for the cross-host hop — legitimate when X is too big for
    one host's devices, but prefer keeping 'data' within a host and
    fanning 'tasks' across hosts when the workload allows it.
    """
    import jax

    local = jax.local_device_count()
    if data_axis_size is None:
        data_axis_size = local
    n_hosts = jax.process_count()
    n_global = local * n_hosts
    within_host = data_axis_size >= 1 and local % data_axis_size == 0
    cross_host = (
        data_axis_size > local
        and data_axis_size % local == 0
        and n_global % data_axis_size == 0
    )
    if not (within_host or cross_host):
        raise ValueError(
            f"data_axis_size={data_axis_size} must divide the local "
            f"device count {local}, or be a multiple of it that divides "
            f"the global device count {n_global}"
        )
    if n_hosts == 1:
        return task_data_mesh(data_axis_size=data_axis_size)
    from jax.sharding import Mesh

    # Deterministic construction (create_hybrid_device_mesh assumes
    # slice-granule topologies and rejects common pod slices): order
    # the global devices by (process, device id) so each contiguous
    # data_axis_size group covers whole processes — within-host groups
    # keep 'data'-axis collectives (gram/gradient psums) on ICI; a
    # cross-host group spans the minimal number of adjacent processes.
    # The 'tasks' axis spans processes over DCN, which is fine because
    # tasks never talk to each other.
    devices = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    arr = np.array(devices).reshape(-1, data_axis_size)
    return Mesh(arr, ("tasks", "data"))


# ---------------------------------------------------------------------------
# declarative named-axis partition rules
# ---------------------------------------------------------------------------

#: Default partition-rule table for streamed data blocks: the design
#: matrix (dense ``X`` or its packed-CSR children ``X/0``/``X/1``) and
#: the per-row vectors (labels, sample weights, fold ids) row-shard
#: onto the mesh 'data' axis; anything unmatched — and every scalar,
#: regardless of rules (the SGD epoch/block clocks) — replicates.
#: Ordered first-match-wins, same contract as the exemplar regex
#: partition tables over named param trees.
STREAM_BLOCK_RULES = (
    (r"(^|/)X($|/)", ("data",)),
    (r"(^|/)(y|sw|fold)($|/)", ("data",)),
    # streamed GBDT margin carry F is (lanes, rows, classes): lane axis
    # replicated (each task lane gathers its own slice), rows sharded
    # on 'data' alongside the binned X block they were computed from
    (r"(^|/)F($|/)", (None, "data")),
)


def _leaf_path_name(path):
    """'/'-joined human name of a pytree leaf path (dict keys, attr
    names, sequence/flattened indices)."""
    parts = []
    for k in path:
        if hasattr(k, "key"):  # DictKey / FlattenedIndexKey
            parts.append(str(k.key))
        elif hasattr(k, "name"):  # GetAttrKey
            parts.append(str(k.name))
        elif hasattr(k, "idx"):  # SequenceKey
            parts.append(str(k.idx))
        else:  # pragma: no cover - future key kinds
            parts.append(str(k))
    return "/".join(parts)


def match_partition_rules(rules, tree, default=()):
    """Declarative named-axis placement: map every leaf of ``tree`` to a
    ``PartitionSpec`` by regex-matching its '/'-joined tree path against
    ``rules`` — an ordered ``(pattern, spec)`` table, first match wins
    (``re.search`` semantics). Specs may be ``PartitionSpec`` instances
    or plain tuples of axis names (``("data",)``); scalar leaves always
    replicate regardless of rules (a scalar has no axis to shard).

    ``default`` is the spec for unmatched non-scalar leaves (replicate
    by default); pass ``default=None`` to make an unmatched leaf a
    loud ``ValueError`` naming the path — the strict mode for param
    trees where silent replication would hide a placement bug.

    Returns a tree of ``PartitionSpec`` with the same structure as
    ``tree`` — the declarative replacement for hand-plumbed per-leaf
    sharding decisions (consumed by ``prepare_streamed`` /
    ``_block_shardings`` on 2D (task × data) meshes).
    """
    import jax
    from jax.sharding import PartitionSpec

    def to_spec(s):
        return s if isinstance(s, PartitionSpec) else PartitionSpec(*s)

    compiled = [(re.compile(pat), to_spec(spec)) for pat, spec in rules]
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    specs = []
    for path, leaf in flat:
        name = _leaf_path_name(path)
        if getattr(leaf, "ndim", 0) == 0:
            specs.append(PartitionSpec())
            continue
        for pat, spec in compiled:
            if pat.search(name):
                specs.append(spec)
                break
        else:
            if default is None:
                raise ValueError(
                    f"no partition rule matches tree path {name!r}"
                )
            specs.append(to_spec(default))
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# elastic meshes (preemptible capacity)
# ---------------------------------------------------------------------------

class ElasticMeshManager:
    """Shrink / resume / re-grow policy for a mesh on preemptible
    capacity — the analogue of Spark dynamic allocation plus executor
    loss handling (the driver kept scheduling on the executors that
    remained, and took preempted ones back when the cluster returned
    them).

    The manager owns the FULL device roster and a partition of it into
    *participants* — the units that are preempted and restored together
    (a host's local devices on multi-process meshes; individual
    devices, or ``group_size`` blocks, on a single-controller mesh).
    Three calls drive the state machine, all invoked by the elastic
    backend (``TPUBackend(elastic=...)``), never by user code:

    - :meth:`on_preempted` — a round classified PREEMPTED: probe which
      participants are lost and rebuild the mesh over the survivors.
      Returns the new (shrunken) mesh, or None when the probe says the
      current mesh already matches (the caller still re-places device
      state either way — preemption presumes it lost).
    - :meth:`maybe_regrow` — called at round boundaries while degraded:
      when the probe reports capacity back, rebuild the larger (up to
      full) mesh. Returns the new mesh or None.
    - :attr:`degraded` — whether the current mesh is smaller than full.

    **Shrink geometry.** Largest-divisor re-layout on BOTH axes: the
    shrunken layout is the (task extent, data size) pair maximising
    devices used, with the task extent a divisor of the FULL task
    extent and the data size a divisor of the full 'data' axis. Ties
    prefer the larger data size, so the per-fit psum geometry — and
    with it bitwise parity against the full mesh — is preserved
    whenever the survivors allow it; only when fewer than
    ``data_axis_size`` devices survive per slot does the data axis
    itself shrink (previously a hard error). The divisor rule is what
    keeps every task axis laid out for the full mesh — padded carries,
    slot-aligned chunks, streamed task trees — placeable on the
    shrunken mesh without re-padding: anything divisible by the full
    extent is divisible by each of its divisors.

    **Probing.** ``probe`` is the seam to real preemption signals
    (plant notifications, heartbeat loss, device health): a callable
    returning the set of currently-LOST participant ids. The default
    consults the installed fault injector's ``lost_participants()``
    (deterministic tests/smokes) and reports nothing lost otherwise —
    on real clusters the PREEMPTED classification itself is the loss
    signal and the operator wires a probe.

    **Multi-host.** ``cluster`` (a dict of ``initialize_cluster``
    kwargs) is the re-init seam for meshes spanning processes: when
    capacity returns, :meth:`rebuild_cluster` tears down and re-joins
    the jax.distributed cluster before the mesh is rebuilt. Today's
    in-process elastic path covers single-controller meshes (a
    shrunken local device set); the multi-process round loop stays
    fail-loud (its collectives cannot be re-synchronised mid-dispatch)
    and resumes through durable checkpoints on restart.
    """

    def __init__(self, devices=None, axis_name="tasks", data_axis_size=1,
                 group_size=None, probe=None, cluster=None,
                 coordinate=None, agree_timeout_s=10.0,
                 kv_namespace="skdist-elastic", heartbeat=None):
        import jax

        if devices is None:
            devices = jax.devices()
        self.full_devices = list(devices)
        self.axis_name = axis_name
        self.data_axis_size = max(1, int(data_axis_size))
        if len(self.full_devices) % self.data_axis_size:
            raise ValueError(
                f"data_axis_size={self.data_axis_size} must divide the "
                f"device count {len(self.full_devices)}"
            )
        self.full_extent = len(self.full_devices) // self.data_axis_size
        self._probe = probe
        self.cluster = dict(cluster) if cluster else None
        # participant partition: by process on multi-process rosters,
        # else group_size blocks (default 1 device = 1 participant)
        n_proc = len({d.process_index for d in self.full_devices})
        self._by_process = group_size is None and n_proc > 1
        if self._by_process:
            self._pid_of = {
                id(d): d.process_index for d in self.full_devices
            }
        else:
            gs = max(1, int(group_size or 1))
            self._pid_of = {
                id(d): i // gs for i, d in enumerate(self.full_devices)
            }
        self.participant_ids = sorted(set(self._pid_of.values()))
        self.current_extent = self.full_extent
        self.current_data = self.data_axis_size
        #: epoch agreement (multi-process coordinated resume): on by
        #: default exactly when participants ARE processes — the only
        #: roster whose loss tears a jax.distributed collective
        self.coordinate = (self._by_process if coordinate is None
                           else bool(coordinate))
        self.agree_timeout_s = float(agree_timeout_s)
        self.kv_namespace = str(kv_namespace)
        self._epoch = 0
        #: participants an epoch agreement declared lost: they stay
        #: lost (no regrow into a dead process) until an operator
        #: ``probe=`` positively reports them back
        self._coordinated_lost = set()
        self._heartbeat = heartbeat
        #: shrink/regrow log: dicts with kind, lost, extents, wall time
        self.events = []
        #: the `mesh` label of this manager's registry gauge — two
        #: elastic backends in one process must not overwrite each
        #: other's extent readings last-writer-wins
        self._obs_id = f"mesh-{next(_MESH_IDS)}"

    # ------------------------------------------------------------------
    @property
    def degraded(self):
        return (self.current_extent < self.full_extent
                or self.current_data < self.data_axis_size)

    def _probe_lost(self):
        """Currently-lost participant ids (a frozenset). An operator
        ``probe=`` is authoritative — a participant it stops reporting
        is considered BACK, including one an epoch agreement declared
        lost. Without a probe, agreement verdicts persist (a dead
        process cannot rejoin a collective on its own) and the default
        consults the installed fault injector."""
        if self._probe is not None:
            lost = frozenset(self._probe())
            self._coordinated_lost &= set(lost)
            return lost
        inj = faults.active_injector()
        probe = getattr(inj, "lost_participants", None)
        lost = frozenset(probe()) if callable(probe) else frozenset()
        return lost | frozenset(self._coordinated_lost)

    def beat(self):
        """Stamp this process's participant heartbeat(s) (``heartbeat=``
        — typically the same :class:`HeartbeatFileProbe` /
        :class:`KVStoreHeartbeatProbe` instance other participants
        probe). Called by the elastic backend at dispatch boundaries;
        a no-op without a heartbeat sink."""
        hb = self._heartbeat
        if hb is None:
            return
        import jax

        try:
            if self._by_process:
                hb.beat(int(jax.process_index()))
            else:
                for p in self.participant_ids:
                    hb.beat(p)
        except Exception as exc:  # a flaky beat must not fail a round
            faults.log_suppressed("ElasticMeshManager.beat", exc,
                                  level=logging.DEBUG)

    def _survivors(self, lost):
        return [d for d in self.full_devices
                if self._pid_of[id(d)] not in lost]

    def _fit_layout(self, n_survivors):
        """Largest-divisor re-layout on BOTH axes (see class
        docstring): the ``(task extent, data size)`` pair maximising
        devices used, the extent a divisor of the full task extent and
        the data size a divisor of the full 'data' axis; ties prefer
        the larger data size (preserving the psum geometry and bitwise
        parity with the full mesh whenever survivors allow). Returns
        ``(0, 0)`` when even one task slot cannot be formed."""
        best = (0, 0)
        for d in range(1, self.data_axis_size + 1):
            if self.data_axis_size % d:
                continue
            for t in range(1, self.full_extent + 1):
                if self.full_extent % t or t * d > n_survivors:
                    continue
                if (t * d, d) > (best[0] * best[1], best[1]):
                    best = (t, d)
        return best

    def _build(self, extent, dsize, survivors):
        from jax.sharding import Mesh

        picked = survivors[: extent * dsize]
        if self.data_axis_size > 1:
            # keep the 2D axis names even at dsize == 1 so compiled
            # programs and PartitionSpecs referencing 'data' stay valid
            arr = np.array(picked).reshape(extent, dsize)
            return Mesh(arr, (self.axis_name, "data"))
        return Mesh(np.array(picked), (self.axis_name,))

    def _resize(self, kind, lost):
        survivors = self._survivors(lost)
        extent, dsize = self._fit_layout(len(survivors))
        if extent == 0:
            raise RuntimeError(
                f"elastic mesh cannot shrink below one task slot: "
                f"{len(survivors)} surviving device(s) for "
                f"data_axis_size={self.data_axis_size} (lost "
                f"participants: {sorted(lost)})"
            )
        if (extent, dsize) == (self.current_extent, self.current_data):
            return None
        mesh = self._build(extent, dsize, survivors)
        self.events.append({
            "kind": kind, "lost": sorted(lost),
            "from_extent": self.current_extent, "to_extent": extent,
            "from_data": self.current_data, "to_data": dsize,
            "t": time.time(),
        })
        logger.warning(
            "elastic mesh %s: task extent %d -> %d, data axis %d -> %d "
            "(lost participants: %s)", kind, self.current_extent, extent,
            self.current_data, dsize, sorted(lost) or "none",
        )
        self.current_extent = extent
        self.current_data = dsize
        faults.record(
            "elastic_shrinks" if kind == "shrink" else "elastic_regrows"
        )
        # the fleet timeline: an elastic resize is an instant on the
        # trace next to the rounds it interrupts, and the mesh extent
        # is a live gauge for the exporters
        obs_trace.instant(
            f"elastic_{kind}",
            {"from": self.events[-1]["from_extent"], "to": extent}
            if obs_trace.enabled() else None,
        )
        obs_metrics.gauge(
            "mesh.task_extent",
            help="current elastic task-axis extent per manager",
        ).set(extent, mesh=self._obs_id)
        obs_metrics.gauge(
            "mesh.data_axis",
            help="current elastic data-axis size per manager",
        ).set(dsize, mesh=self._obs_id)
        return mesh

    # ------------------------------------------------------------------
    def on_preempted(self):
        """A PREEMPTED round: rebuild over the survivors. Returns the
        shrunken mesh or None when the extent is unchanged (the caller
        re-places shared state either way)."""
        return self._resize("shrink", self._probe_lost())

    @property
    def can_coordinate(self):
        """Whether :meth:`coordinated_resume` is available: opted in,
        process-partitioned roster, and a live jax.distributed KV
        client to agree through."""
        return (self.coordinate and self._by_process
                and _kv_client() is not None)

    def coordinated_resume(self, local_prefix):
        """Epoch agreement for a PREEMPTED multi-process round: the
        survivors agree on **(epoch, gathered-task-prefix, survivor
        roster)** through the jax.distributed KV store, then the mesh
        re-forms over the survivors' devices — so a multi-process
        search resumes mid-round instead of failing loud to a durable
        checkpoint restart.

        Protocol (every surviving process runs it symmetrically):

        1. bump the per-manager epoch (survivors see the same fault
           sequence, so epochs advance in lockstep) and publish this
           process's contiguous gathered prefix under
           ``{ns}/e{epoch}/p{pid}``;
        2. blocking-get every other participant's key with the
           ``agree_timeout_s`` budget — a process that never publishes
           within it is DECLARED LOST (the KV silence doubles as the
           preemption probe; a configured ``probe=`` / injector signal
           merges in);
        3. the agreed resume prefix is the MIN over the survivors'
           prefixes (SPMD lockstep makes them equal in practice; min
           is the safe direction — re-running a gathered task is
           correct, skipping an ungathered one is not);
        4. the mesh rebuilds over the survivors at the
           largest-divisor task extent (the ordinary shrink
           geometry). New collectives then compile against the
           survivor mesh — the collective "re-forms" lazily through
           the same structural-cache path every elastic resize uses.

        Returns ``(agreed_prefix, mesh_or_None)`` (None: extent
        unchanged — a transient where everyone responded; the caller
        still re-places shared state).

        Caveats, documented honestly: the agreement rides the
        EXISTING distributed service, so it requires the coordinator
        process to survive (coordinator loss raises, and the caller
        falls back to the fail-loud checkpoint remedy); and a
        participant publishing within epsilon of a peer's timeout
        expiry can be declared lost by one survivor and seen by
        another — the timeout is the roster authority, size it well
        above the fleet's straggler spread. Lost participants stay
        lost (no regrow) until an operator ``probe=`` reports them
        back; re-admitting a RESTARTED process goes through the
        ``cluster=`` re-``initialize_cluster`` seam
        (:meth:`rebuild_cluster`) at regrow time."""
        import jax

        client = _kv_client()
        if client is None:
            raise RuntimeError(
                "coordinated elastic resume needs the jax.distributed "
                "KV store; initialize_cluster was never called (or the "
                "coordinator is gone)"
            )
        self._epoch += 1
        epoch = self._epoch
        me = int(jax.process_index())
        ns = f"{self.kv_namespace}/e{epoch}"
        # the trace context rides the SAME KV round trip as the prefix:
        # every survivor publishes its active context (or a fresh one),
        # and all adopt the minimum-id survivor's trace id — so the
        # stitched multi-process trace shows ONE epoch-agreement line
        # across every process's track instead of per-process orphans
        my_ctx = obs_trace.current_context() or (
            obs_trace.new_context() if obs_trace.enabled() else None
        )
        client.key_value_set(
            f"{ns}/p{me}",
            json.dumps({"prefix": int(local_prefix), "trace": my_ctx}),
            allow_overwrite=True,
        )
        prefixes = {me: int(local_prefix)}
        traces = {me: my_ctx}
        lost = set()
        timeout_ms = max(1, int(self.agree_timeout_s * 1e3))
        for pid in self.participant_ids:
            if pid == me:
                continue
            try:
                raw = client.blocking_key_value_get(
                    f"{ns}/p{pid}", timeout_ms
                )
                peer = json.loads(raw)
                prefixes[pid] = int(peer["prefix"])
                traces[pid] = peer.get("trace")
            except Exception:
                lost.add(pid)
        lost |= set(self._probe_lost())
        lost.discard(me)
        self._coordinated_lost |= lost
        survivors = sorted(set(prefixes) - lost)
        agreed = min(prefixes[pid] for pid in survivors)
        faults.record("elastic_epoch_agreements")
        self.events.append({
            "kind": "epoch_agreement", "epoch": epoch,
            "prefix": int(agreed), "survivors": survivors,
            "lost": sorted(lost), "t": time.time(),
        })
        logger.warning(
            "elastic epoch %d agreement: survivors=%s lost=%s -> resume "
            "from task prefix %d", epoch, survivors, sorted(lost), agreed,
        )
        agreed_ctx = next(
            (traces[pid] for pid in survivors if traces.get(pid)), None
        )
        with obs_trace.use_context(agreed_ctx):
            obs_trace.instant(
                "elastic_epoch_agreement",
                {"epoch": epoch, "prefix": int(agreed),
                 "survivors": len(survivors), "lost": len(lost)}
                if obs_trace.enabled() else None,
            )
        mesh = self._resize("shrink", frozenset(self._coordinated_lost)) \
            if lost else None
        return int(agreed), mesh

    def maybe_regrow(self):
        """Round-boundary check while degraded: when the probe reports
        capacity back, rebuild the larger mesh (re-joining the cluster
        first where configured). Returns the new mesh or None."""
        if not self.degraded:
            return None
        lost = self._probe_lost()
        survivors = self._survivors(lost)
        extent, dsize = self._fit_layout(len(survivors))
        if extent * dsize <= self.current_extent * self.current_data:
            return None
        if self.cluster is not None:
            self.rebuild_cluster()
        return self._resize("regrow", lost)

    def rebuild_cluster(self):
        """Re-join the jax.distributed cluster (the multi-host 'regrow'
        leg: restored hosts re-initialize into the global device set).
        A no-op failure is logged, not fatal — the local device roster
        still regrows."""
        import jax

        try:
            jax.distributed.shutdown()
        except Exception as exc:  # not initialised / already down
            faults.log_suppressed("ElasticMeshManager.shutdown", exc,
                                  level=logging.DEBUG)
        try:
            initialize_cluster(**self.cluster)
        except Exception as exc:
            faults.log_suppressed("ElasticMeshManager.reinit", exc)


def _kv_client():
    """The jax.distributed KV-store client, or None when the cluster
    was never initialized (single-controller runs)."""
    from jax._src import distributed

    return distributed.global_state.client


# ---------------------------------------------------------------------------
# production preemption probes (the manager's `probe=` seam)
# ---------------------------------------------------------------------------

class HeartbeatFileProbe:
    """Heartbeat-file liveness for process participants: every
    participant :meth:`beat`\\ s its file (an mtime touch on shared
    storage) at dispatch boundaries, and the probe reports any
    participant whose file is missing or staler than ``stale_s`` as
    LOST. The plainest production probe — no coordinator dependency,
    so it keeps working through the exact failures it detects. Pass
    the same instance as both ``heartbeat=`` (this process beats) and
    ``probe=`` (this process judges the others) of an
    :class:`ElasticMeshManager`. Beat once at startup: a participant
    that never wrote its file reads as lost, which is the right
    default for a worker that never came up."""

    def __init__(self, directory, participants, stale_s=30.0,
                 clock=time.time):
        self.directory = str(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.participants = sorted(int(p) for p in participants)
        self.stale_s = float(stale_s)
        self._clock = clock

    def path(self, participant):
        return os.path.join(self.directory,
                            f"participant-{int(participant)}.hb")

    def beat(self, participant):
        p = self.path(participant)
        with open(p, "a", encoding="utf-8"):
            pass
        now = self._clock()
        os.utime(p, (now, now))

    def __call__(self):
        now = self._clock()
        lost = set()
        for p in self.participants:
            try:
                mtime = os.stat(self.path(p)).st_mtime
            except OSError:
                lost.add(p)
                continue
            if now - mtime > self.stale_s:
                lost.add(p)
        return lost


class KVStoreHeartbeatProbe:
    """Heartbeats through the jax.distributed KV store: each process
    :meth:`beat`\\ s a wall-clock stamp under its participant key;
    the probe reports missing/stale stamps as lost. The zero-extra-
    infrastructure variant of :class:`HeartbeatFileProbe` for fleets
    already running a coordinator — with the same caveat the epoch
    agreement carries: it shares fate with the coordinator process."""

    def __init__(self, participants, stale_s=30.0,
                 namespace="skdist-hb", clock=time.time):
        self.participants = sorted(int(p) for p in participants)
        self.stale_s = float(stale_s)
        self.namespace = str(namespace)
        self._clock = clock

    def _key(self, participant):
        return f"{self.namespace}/p{int(participant)}"

    def beat(self, participant):
        client = _kv_client()
        if client is None:
            raise RuntimeError(
                "KVStoreHeartbeatProbe needs an initialized "
                "jax.distributed cluster"
            )
        client.key_value_set(self._key(participant),
                             repr(float(self._clock())),
                             allow_overwrite=True)

    def __call__(self):
        client = _kv_client()
        if client is None:
            return set(self.participants)
        now = self._clock()
        lost = set()
        for p in self.participants:
            try:
                raw = client.blocking_key_value_get(self._key(p), 50)
                if now - float(raw) > self.stale_s:
                    lost.add(p)
            except Exception:
                lost.add(p)
        return lost


class MaintenanceEventProbe:
    """Pluggable maintenance-event hook: ``hook()`` returns the
    participant ids a platform notice says are being (or about to be)
    preempted — e.g. a poll of the cloud metadata maintenance-event
    endpoint, or a callback queue an operator daemon feeds. Each
    report is HELD for ``hold_s`` so a one-shot notice outlives the
    round that happens to read it; after the hold the participant is
    presumed back (pair with a heartbeat probe via
    :func:`combine_probes` when "gone" must be observed, not
    presumed)."""

    def __init__(self, hook, hold_s=120.0, clock=time.time):
        self.hook = hook
        self.hold_s = float(hold_s)
        self._clock = clock
        self._until = {}

    def __call__(self):
        now = self._clock()
        for p in (self.hook() or ()):
            self._until[int(p)] = now + self.hold_s
        return {p for p, t in self._until.items() if t > now}


def combine_probes(*probes):
    """One probe from many: the union of every probe's lost set (a
    participant is lost if ANY signal says so — heartbeat silence OR a
    maintenance notice)."""

    def combined():
        lost = set()
        for probe in probes:
            lost |= set(probe())
        return lost

    return combined
