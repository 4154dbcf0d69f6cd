"""
Task backends: where sk-dist had exactly one fan-out idiom —
``sc.parallelize(tasks, numSlices).map(closure).collect()`` with
``sc.broadcast`` for shared read-only data (reference
``search.py:411-437``) — skdist_tpu has two execution paths behind one
interface:

1. ``run_tasks(fn, tasks)``: generic host fan-out for arbitrary Python
   task closures (any sklearn-compatible estimator). Thread-pooled; the
   analogue of the reference's joblib fallback *and* of Spark executors
   for non-JAX estimators.

2. ``batched_map(kernel, task_args, shared_args)``: the TPU-native path.
   Tasks that are *many fits of the same XLA program* are stacked on a
   leading task axis, ``vmap``-ed into one kernel, ``jit``-compiled with
   the task axis sharded over a device mesh, and executed in chunks
   ("rounds") sized to the device count. Shared (X, y) is device-resident
   and replicated — the broadcast analogue — and results gather over ICI
   into host numpy, the ``collect()`` analogue.

``backend=None`` on any estimator resolves to a serial LocalBackend,
mirroring the reference's ``sc=None`` joblib path (search.py:388-408) so
unit tests need no accelerator.
"""

import collections
import logging
import math
import os
import re
import time
import warnings
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import compile_cache, faults
from ..obs import metrics as obs_metrics, trace as obs_trace


def _env_flag(name):
    return os.environ.get(name, "").strip().lower() in ("1", "true", "yes")


def prefers_host_engine(backend, estimator):
    """True when a batched dispatch should yield to the host fan-out
    because the estimator resolves to its f64 BLAS host engine on this
    backend (``engine='auto'`` on a CPU platform, or ``engine='host'``).

    Consulted by EVERY batched-path gate (search, multiclass,
    eliminate) so one estimator never silently runs two different
    numerical engines depending on which meta-estimator wraps it
    (round-5 review). An EXPLICIT ``engine='host'`` pin wins even over
    a device backend (the fan-out then rides the backend's generic
    host ``run_tasks`` leg — ignoring the pin would select candidates
    with one engine and refit the winner with another); ``'auto'`` on
    a device backend always chooses the batched mesh program."""
    resolve = getattr(estimator, "_resolve_host_engine", None)
    if resolve is None:
        return False
    if getattr(estimator, "engine", None) == "host":
        return True
    if getattr(backend, "is_device_backend", False):
        return False
    return bool(resolve())


def tree_nbytes(tree, per_device=False):
    """Total leaf bytes of a pytree — the placement layer's shared-data
    byte accounting (registered pytree containers like
    ``sparse.PackedX`` contribute their actual leaves). ``per_device``:
    of a PLACED tree, what one device holds — a sharded leaf counts
    its shard, a replicated one the whole."""
    import jax

    def shape(leaf):
        if per_device and hasattr(leaf, "sharding"):
            return leaf.sharding.shard_shape(leaf.shape)
        return leaf.shape

    return int(sum(
        int(np.prod(shape(l))) * np.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(tree)
        if hasattr(l, "shape")
    ))


def parse_partitions(partitions, n_tasks):
    """Resolve a partition policy to a device-round size.

    The reference ``_parse_partitions`` (base.py:53-64) turned
    ``partitions`` into a Spark ``numSlices``: 'auto'/None → one task
    per slice. The TPU analogue of a "slice" is a *round* of the
    batched program; more partitions → smaller rounds (finer
    granularity, less HBM per round). 'auto'/None → a single full
    round (all tasks in one XLA program — the preferred policy).

    Returns the number of tasks per round.
    """
    if partitions == "auto" or partitions is None:
        return n_tasks
    return max(1, -(-n_tasks // int(partitions)))


def get_value(obj):
    """Unwrap a broadcast handle (reference ``_get_value``, base.py:67-72).

    Backends may hand shared data to task closures either directly or as
    a zero-arg handle; task code calls ``get_value`` and stays agnostic,
    exactly like the reference's broadcast-transparent closures.
    """
    if isinstance(obj, _BroadcastHandle):
        return obj.value
    return obj


class _BroadcastHandle:
    """Host-side handle to shared read-only task data."""

    __slots__ = ("value",)

    def __init__(self, value):
        self.value = value


class TaskBackend:
    """Interface for fan-out execution."""

    #: whether batched_map dispatches onto accelerator devices
    is_device_backend = False

    def broadcast(self, value):
        return _BroadcastHandle(value)

    #: scheduler stats of the most recent batched_map call (mode,
    #: rounds, dispatch_s, gather_wait_s) — benchmark / diagnostic
    #: observability for the pipelined round scheduler
    last_round_stats = None

    #: total leaf bytes of the most recently placed shared-data tree —
    #: the placement layer's byte accounting. A packed-CSR leaf pair
    #: (``sparse.PackedX``) contributes its idx+val bytes, NOT its
    #: logical dense size, so this is the number that shows the sparse
    #: plane's device-memory win (and what the sparse fit smoke
    #: asserts shrank). On a mesh with a ``data`` axis it counts ONE
    #: device's share (a row-sharded leaf its shard), which is what
    #: round sizing holds against one device's free memory
    last_shared_bytes = None

    def run_tasks(self, fn, tasks, verbose=0):
        raise NotImplementedError

    def batched_map(self, kernel, task_args, shared_args=(), static_args=None,
                    round_size=None, shared_specs=None, return_timings=False,
                    pad_to_round=False, cache_key=None):
        raise NotImplementedError

    def prepare_batched(self, kernel, shared_args=(), static_args=None,
                        shared_specs=None, cache_key=None):
        raise NotImplementedError

    #: whether batched_map_iterative runs the convergence-compacted
    #: slice loop on this backend (False falls back to the spec's
    #: classic kernel)
    supports_iterative = False

    def batched_map_iterative(self, spec, task_args, shared_args=(),
                              static_args=None, round_size=None,
                              shared_specs=None, return_timings=False,
                              cache_key=None, on_round=None, rung=None):
        """Convergence-compacted execution of an iterative kernel (see
        :class:`IterativeKernelSpec`). Backends without the slice loop
        run the spec's fallback kernel through :meth:`batched_map` —
        the fallback is EXHAUSTIVE, so an adaptive ``rung`` controller
        is reset (its ``killed`` map must stay empty: every lane runs
        to completion here)."""
        if spec.fallback is None:
            raise NotImplementedError(
                f"{type(self).__name__} has no iterative slice loop and "
                "the spec carries no fallback kernel"
            )
        if rung is not None:
            rung.deactivate()
        return self.batched_map(
            spec.fallback, task_args, shared_args,
            static_args=static_args, round_size=round_size,
            shared_specs=shared_specs, return_timings=return_timings,
            cache_key=spec.fallback_cache_key or cache_key,
            on_round=on_round,
        )

    #: task slots per round on the mapped axis (device count on mesh
    #: backends); BatchedPlan callers shape their task axis to this
    n_task_slots = 1

    #: the elastic-mesh manager (``TPUBackend(elastic=...)``); None on
    #: backends without preemptible capacity
    elastic = None

    def elastic_preempted(self):
        """PREEMPTED seen by a caller-owned dispatch loop: hook for
        elastic backends to shrink their mesh. Base backends have no
        mesh to shrink — False means "nothing changed, just
        re-place"."""
        return False

    def elastic_regrow_check(self):
        """Round-boundary regrow probe; False on non-elastic backends."""
        return False

    def _free_device_bytes(self):
        """Free memory on the execution device, or None where the
        backend reports no stats (host/CPU backends)."""
        return None

    def hbm_round_cap(self, bytes_per_task, headroom=0.85):
        """Largest per-round task count whose in-flight footprint fits
        free device memory — the same linear estimate ``batched_map``'s
        proactive round sizing applies after compiling, exposed so
        callers (the serving registry's shape buckets) can cap shapes
        BEFORE committing to compile them. ``bytes_per_task`` counts
        one task's argument + output bytes — compute it with
        :func:`tree_nbytes` so registered containers (the sparse
        plane's packed idx/val pairs) are billed at their true leaf
        bytes, not their logical dense size; the cap budgets
        ``_MAX_ROUNDS_IN_FLIGHT`` rounds of them inside ``headroom`` of
        free memory (temps are unknowable without compiling — callers
        wanting exactness still get the reactive backstop). Returns
        None when the device reports no memory stats (CPU)."""
        free = self._free_device_bytes()
        if free is None or free <= 0 or bytes_per_task <= 0:
            return None
        cap = int(free * headroom) // (
            _MAX_ROUNDS_IN_FLIGHT * int(bytes_per_task)
        )
        return max(1, cap)

    # fitted estimators must never hold a live backend; give pickle a
    # loud failure instead of a corrupt artifact
    def __reduce__(self):
        raise TypeError(
            f"{type(self).__name__} holds live runtime state and cannot be "
            "pickled; fitted estimators strip it automatically."
        )


class IterativeKernelSpec:
    """An iterative (convergence-aware) batched kernel, in three parts:

    - ``init(shared, task) -> carry``: start one task's solve and run
      its first iteration slice; the carry is a dict pytree whose
      ``done_key`` leaf (a scalar bool per task) means "no further step
      can change this task".
    - ``step(shared, task, carry) -> carry``: advance one more slice.
    - ``finalize(shared, task, carry) -> outputs``: shape the final
      per-task outputs. Only the ``finalize_keys`` leaves of the carry
      are consumed — retired lanes' remaining solver state (e.g. the
      L-BFGS S/Y history) never needs to leave the device.

    ``score(shared, task, carry) -> scalar`` is the OPTIONAL rung
    evaluator of the adaptive (ASHA) scheduler: a quality readout of a
    LIVE carry (typically: shape params from the current iterate, score
    the held-out fold). It is compiled as a fourth jit entry next to
    init/step/finalize — carries never leave the device; only the
    ``(n_lanes,)`` score vector is gathered, riding the same flags-only
    D2H path as the done flags. It must be a pure function of its
    inputs (it runs zero or more times per slice depending on the rung
    cadence, and never between a step and the carry it produced).

    ``fallback`` is the classic all-iterations kernel with the same
    outputs (and ``fallback_cache_key`` its compile-cache key): the
    scheduler downgrades to a plain :meth:`TaskBackend.batched_map` of
    it on backends without the slice loop, on multi-process meshes
    (per-slice host compaction decisions would need cross-process
    agreement), and when a compacted round exhausts device memory.

    ``count_keys`` optionally names the carry's per-lane work counters,
    ``(iterations, evaluations)``: the compacted loop gathers them with
    the finalize leaves as lanes retire and books them, one int per
    task, under ``iters`` / ``fevals`` of its RoundStats — together
    with the lane-slot occupancy (``lane_slots`` / ``live_lane_slots``).
    A spec without them leaves all four ``None``.
    """

    __slots__ = ("init", "step", "finalize", "finalize_keys", "done_key",
                 "fallback", "fallback_cache_key", "score", "count_keys")

    def __init__(self, init, step, finalize, finalize_keys,
                 done_key="done", fallback=None, fallback_cache_key=None,
                 score=None, count_keys=()):
        self.init = init
        self.step = step
        self.finalize = finalize
        self.finalize_keys = tuple(finalize_keys)
        self.done_key = done_key
        self.fallback = fallback
        self.fallback_cache_key = fallback_cache_key
        self.score = score
        self.count_keys = tuple(count_keys)


class IterativePlan:
    """The :class:`BatchedPlan` counterpart for iterative kernels:
    shardings resolved, shared args device-resident, and the three jit
    entries (init slice / step slice / finalize) memoised — built once
    by ``prepare_batched_iterative`` and driven by the compacted round
    loop (:func:`_run_compacted`)."""

    __slots__ = ("init_fn", "step_fn", "fin_fn", "score_fn", "shared",
                 "put", "n_task_slots", "data_shards", "_shared_sig")

    def __init__(self, init_fn, step_fn, fin_fn, score_fn, shared, put,
                 n_task_slots=1, data_shards=1):
        self.init_fn = init_fn
        self.step_fn = step_fn
        self.fin_fn = fin_fn
        self.score_fn = score_fn  # None unless the spec carries a rung
        self.shared = shared
        self.put = put
        self.n_task_slots = n_task_slots
        # devices that share the rows of each shared operand
        self.data_shards = data_shards
        self._shared_sig = compile_cache.shape_sig(shared)


def _iterative_jit_entries(spec, static_args, task_sharding,
                           shared_shardings, cache_key):
    """The memoised jit entries of an iterative kernel (three, plus a
    fourth rung-score entry when the spec carries one). The step,
    finalize and score kernels see ``{"task": ..., "carry": ...}`` as
    their task tree so the whole existing task-axis machinery (vmap,
    task sharding, AOT-per-chunk memo) applies unchanged; the carry
    rides the task axis like any other per-task leaf.

    Donation is deliberately OFF for these entries: the slice loop
    feeds each step's output carry back as the next step's input while
    the host still holds the round's done flags (and, at compaction,
    gathered carry leaves) — on the CPU backend those host reads can be
    zero-copy views of the very buffers donation would recycle, and the
    self-feedback chain was measured to corrupt carries (wrong-task
    trajectories) under exactly that pattern. The classic path keeps
    donation: its inputs are one-shot host slices nothing reads back.
    """

    def init_kernel(shared, task):
        return spec.init(shared, task)

    def step_kernel(shared, tc):
        return spec.step(shared, tc["task"], tc["carry"])

    def fin_kernel(shared, tc):
        return spec.finalize(shared, tc["task"], tc["carry"])

    def key(part):
        return ("iter", part, cache_key) if cache_key is not None else None

    if spec.score is not None:
        def score_kernel(shared, tc):
            return spec.score(shared, tc["task"], tc["carry"])

        score_fn = _jit_vmapped(score_kernel, static_args, task_sharding,
                                shared_shardings, key("score"), False)
    else:
        score_fn = None
    return (
        _jit_vmapped(init_kernel, static_args, task_sharding,
                     shared_shardings, key("init"), False),
        _jit_vmapped(step_kernel, static_args, task_sharding,
                     shared_shardings, key("step"), False),
        _jit_vmapped(fin_kernel, static_args, task_sharding,
                     shared_shardings, key("fin"), False),
        score_fn,
    )


class RungController:
    """Host-side ASHA rung policy for the compacted slice loop
    (asynchronous successive halving — Li et al., MLSys 2020).

    Every ``every`` slices the scheduler scores all LIVE carries with
    the spec's rung-score kernel and hands the ``(lane_id, score)``
    pairs to :meth:`decide`, which kills the bottom ``1 - 1/eta``
    *groups* (a group is typically one candidate's CV-fold lanes, so a
    candidate's folds live and die together — ``groups=None`` makes
    every lane its own group). Killed lanes are marked done and retire
    through the ordinary done-flag/compaction path, so freed rounds
    collapse immediately.

    Scores are GREATER-IS-BETTER (the device scorers' convention; the
    ``neg_*`` regression metrics are already negated). Non-finite
    scores rank below every finite score — a diverged lane is the
    first thing a rung eliminates. ``eta=inf`` scores every rung but
    never kills (the parity-pinned "observe only" mode). Ties break
    deterministically toward the smaller group id.

    The controller is single-use per *attempt*: the fault-retry loop
    calls :meth:`reset` before re-running (carries restart from
    scratch, so rung history must too), and the classic-fallback path
    resets it as well — a downgraded dispatch is exhaustive, and a
    stale ``killed`` map would wrongly error-score lanes that ran to
    completion.
    """

    def __init__(self, eta=3.0, every=1, groups=None):
        eta = float(eta)
        if not eta > 1.0:
            raise ValueError(f"rung eta must be > 1 (got {eta!r})")
        every = int(every)
        if every < 1:
            raise ValueError(f"rung cadence must be >= 1 (got {every!r})")
        self.eta = eta
        self.every = every
        self.groups = None if groups is None else np.asarray(groups)
        #: lane id -> rung index at which the lane was killed
        self.killed = {}
        #: per-rung observability: {"rung", "slice", "n_live",
        #: "n_groups", "n_killed"} (lane counts)
        self.history = []
        #: False once a backend downgrade ran the exhaustive fallback —
        #: the caller's "adaptive engaged" signal (a retry-loop reset
        #: keeps it True: the re-attempt still races rungs)
        self.active = True

    def reset(self):
        self.killed = {}
        self.history = []

    def deactivate(self):
        """A downgrade to exhaustive execution: clear every verdict AND
        mark the controller inactive so the caller warns instead of
        silently reporting an adaptive race that never ran."""
        self.reset()
        self.active = False

    def due(self, slice_idx):
        """Whether a rung fires after slice ``slice_idx`` (1-based)."""
        return slice_idx % self.every == 0

    def decide(self, live_ids, scores, slice_idx):
        """One rung: given the live lanes' ids and rung scores, pick the
        lanes to kill. Returns the killed lane ids (possibly empty) and
        records them in :attr:`killed` / :attr:`history`."""
        live_ids = np.asarray(live_ids)
        scores = np.asarray(scores, dtype=np.float64)
        rung = len(self.history)
        gids = (
            self.groups[live_ids] if self.groups is not None else live_ids
        )
        uniq, inv = np.unique(gids, return_inverse=True)
        n_groups = len(uniq)
        entry = {
            "rung": rung, "slice": int(slice_idx),
            "n_live": int(live_ids.size), "n_groups": int(n_groups),
            "n_killed": 0,
        }
        self.history.append(entry)
        if live_ids.size == 0 or not math.isfinite(self.eta):
            return live_ids[:0]
        # group score = mean over the group's live lanes; non-finite
        # lanes drag their group to -inf (kill divergence first)
        s = np.where(np.isfinite(scores), scores, -np.inf)
        gsum = np.zeros(n_groups)
        gcnt = np.zeros(n_groups)
        np.add.at(gsum, inv, s)
        np.add.at(gcnt, inv, 1.0)
        with np.errstate(invalid="ignore"):
            gmean = gsum / gcnt
        gmean = np.where(np.isfinite(gmean), gmean, -np.inf)
        # ceil(n_groups / eta) in float: eta is any real > 1 (a
        # truncating int(eta) would make eta in (1, 2) keep everything)
        n_keep = max(1, int(math.ceil(n_groups / self.eta)))
        if n_keep >= n_groups:
            return live_ids[:0]
        # deterministic: sort by (-score, group id) — lexsort, last key
        # primary — and kill everything past the keep set
        order = np.lexsort((uniq, -gmean))
        killed_groups = uniq[order[n_keep:]]
        kill_mask = np.isin(gids, killed_groups)
        killed_ids = live_ids[kill_mask]
        for lid in killed_ids:
            self.killed[int(lid)] = rung
        entry["n_killed"] = int(killed_ids.size)
        return killed_ids


#: smallest task set the convergence-compacted path engages for — below
#: this so few lanes converge apart that neither retiring them early
#: nor merging their rounds buys back the three slice-loop programs
#: that would still have to compile (the classic fused kernel also
#: stays the bitwise-pinned reference path for the small parity tests)
MIN_ITER_TASKS = 24


def compaction_enabled():
    """The convergence-compacted batched path is ON by default for
    estimators that support iteration-sliced fits;
    ``SKDIST_COMPACTION=0`` is the kill switch back to the classic
    all-iterations-fused path."""
    return os.environ.get("SKDIST_COMPACTION", "").strip().lower() not in (
        "0", "false", "no",
    )


def resolve_slice_iters(max_iter):
    """Iterations per slice of the compacted path: ``SKDIST_SLICE_ITERS``
    when set, else ~1/8 of the iteration budget (floor 4 — where a
    round's flags are read in step, a slice boundary leaves the v5e
    idle about 3 ms: the flags' D2H after the device's end, 0.1 ms of
    host, and a dispatch of 1.2–1.5 ms whose program starts before it
    returns; shorter slices pay that more often than lanes finish. A
    lone round that reads its flags one slice behind pays it under
    device work).
    """
    env = os.environ.get("SKDIST_SLICE_ITERS", "").strip()
    if env:
        try:
            n = int(env)
        except ValueError:
            n = 0
        if n > 0:
            return n
    return max(4, -(-int(max_iter) // 8))


def iterative_fit_supported(backend, est_cls, n_tasks, max_iter):
    """The ONE gate every batched call site (search, OvR, OvO) asks
    before taking the convergence-compacted path: returns the slice
    size to use, or None for the classic fused kernel. Engages when the
    estimator family exposes iteration-sliced fit kernels, the backend
    runs the slice loop, the task set spans several rounds, and the
    iteration budget is worth slicing."""
    if not compaction_enabled():
        return None
    if not getattr(backend, "supports_iterative", False):
        return None
    if not getattr(est_cls, "_supports_sliced_fit", False):
        return None
    if not hasattr(est_cls, "_build_fit_slice_kernels"):
        return None
    if n_tasks < max(MIN_ITER_TASKS,
                     2 * getattr(backend, "n_task_slots", 1)):
        return None
    if not max_iter:
        return None
    n_slice = resolve_slice_iters(max_iter)
    if n_slice >= int(max_iter):
        return None
    return n_slice


def _iterative_chunk(n_tasks, n_slots, shared_bytes, lane_bytes, lanes_fit,
                     target_rounds=8):
    """``(chunk, basis)`` of :func:`iterative_chunk_size`: the round
    size and the name of the rule that set it."""
    per_slot = -(-n_tasks // n_slots)
    amortise = -(-int(shared_bytes) // max(1, int(lane_bytes)))
    merge = -(-(-(-n_tasks // target_rounds)) // n_slots)
    want = max(1, amortise, merge)
    fit = per_slot if lanes_fit is None else max(1, lanes_fit // n_slots)
    if fit < min(per_slot, want):
        basis = "memory"
    elif want >= per_slot:
        basis = "all_tasks"
    elif amortise > merge:
        basis = "amortised"
    else:
        basis = "target_rounds"
    width = min(per_slot, fit, want)
    # as many rounds as that width asks for, evenly filled
    width = -(-per_slot // -(-per_slot // width))
    return int(width * n_slots), basis


def iterative_chunk_size(n_tasks, n_slots, shared_bytes=0, lane_bytes=0,
                         lanes_fit=None, target_rounds=8):
    """Default round size of the compacted path, from the dispatch's
    own shapes.

    Every pass of a solver step reads the shared operands once per
    ROUND, whatever the round's width, so cutting a task set into more
    rounds is free only where a round's cost follows its lanes: a round
    grows until its lanes' own bytes (``lane_bytes`` each: task slice,
    carry, temporaries) match ``shared_bytes``, or it holds every task.
    ``lanes_fit`` — the lanes device memory holds, None where the
    device reports none — caps it. Where lanes outweigh the shared
    operands the answer is what it always was: about ``target_rounds``
    rounds, so live-task compaction has rounds to merge without paying
    dispatch for hundreds of tiny ones. Rounds are multiples of
    ``n_slots``.

    A round that holds every task never shrinks as its lanes finish —
    there is no other round to merge it with — and where the shared
    read bounds a pass that costs nothing: 41 live lanes of 50 take the
    time 50 take."""
    return _iterative_chunk(n_tasks, n_slots, shared_bytes, lane_bytes,
                            lanes_fit, target_rounds)[0]


class LocalBackend(TaskBackend):
    """Host execution: serial (n_jobs=1) or thread-pooled.

    Threads, not processes: the heavy lifting inside tasks is either XLA
    (releases the GIL) or sklearn native code (releases the GIL), and
    thread fan-out avoids pickling the training data per task — the same
    reason the reference broadcasts instead of shipping X per task.
    """

    def __init__(self, n_jobs=None, sync_rounds=None):
        self.n_jobs = n_jobs
        self.sync_rounds = (
            _env_flag("SKDIST_SYNC_ROUNDS") if sync_rounds is None
            else bool(sync_rounds)
        )
        compile_cache.enable_disk_cache()

    def _effective_jobs(self, n_tasks):
        n_jobs = self.n_jobs
        if n_jobs in (None, 0):
            return 1
        if n_jobs < 0:
            return max(1, min(n_tasks, (os.cpu_count() or 1) + 1 + n_jobs))
        return max(1, min(n_tasks, n_jobs))

    def run_tasks(self, fn, tasks, verbose=0):
        tasks = list(tasks)
        n_jobs = self._effective_jobs(len(tasks))
        if n_jobs == 1:
            return [fn(t) for t in tasks]
        with ThreadPoolExecutor(max_workers=n_jobs) as pool:
            return list(pool.map(fn, tasks))

    def prepare_batched(self, kernel, shared_args=(), static_args=None,
                        shared_specs=None, cache_key=None):
        """Build a :class:`BatchedPlan` for repeated single-round
        dispatches: the jit entry is memoised once and shared args are
        staged on the default device up front, so per-call work is
        placement of the task slice + execution — the serving hot path.
        """
        import jax
        import jax.numpy as jnp

        fn = _jit_vmapped(kernel, static_args, None, None, cache_key, False)
        shared_args = jax.tree_util.tree_map(jnp.asarray, shared_args)
        self.last_shared_bytes = tree_nbytes(shared_args)
        return BatchedPlan(fn, shared_args, lambda t: t, n_task_slots=1)

    supports_iterative = True

    def prepare_streamed(self, kernel, block_example=None,
                         static_args=None, cache_key=None,
                         partition_rules=None):
        """Jit entry + placement fns for a block-streamed dispatch
        (``kernel(block, task)``; tasks vmapped on the leading axis):
        the task tree is placed once by the caller, the shared tree —
        one data block — per block by a :class:`BlockFeeder`.
        ``partition_rules`` is accepted for signature parity with the
        mesh backend and ignored (no mesh to place onto)."""
        import jax
        import jax.numpy as jnp

        fn = _jit_vmapped(kernel, static_args, None, None, cache_key,
                          False)
        put = lambda t: jax.tree_util.tree_map(jnp.asarray, t)
        return StreamPlan(fn, put, put, n_task_slots=1)

    def prepare_batched_iterative(self, spec, shared_args=(),
                                  static_args=None, shared_specs=None,
                                  cache_key=None):
        import jax
        import jax.numpy as jnp

        fns = _iterative_jit_entries(
            spec, static_args, None, None, cache_key
        )
        shared_args = jax.tree_util.tree_map(jnp.asarray, shared_args)
        self.last_shared_bytes = tree_nbytes(shared_args)
        return IterativePlan(*fns, shared_args, lambda t: t, n_task_slots=1)

    def batched_map_iterative(self, spec, task_args, shared_args=(),
                              static_args=None, round_size=None,
                              shared_specs=None, return_timings=False,
                              cache_key=None, on_round=None, rung=None):
        """Convergence-compacted execution on the host device: same
        slice/compact/finalize loop as the mesh backend, single task
        slot."""
        n_tasks = _leading_dim(task_args)
        plan = self.prepare_batched_iterative(
            spec, shared_args, static_args, shared_specs, cache_key
        )
        return _dispatch_iterative(
            self, plan, spec, task_args, shared_args, static_args,
            shared_specs, n_tasks,
            _size_iterative_round(self, plan, task_args, n_tasks,
                                  round_size),
            return_timings, cache_key, on_round=on_round, rung=rung,
        )

    def batched_map(self, kernel, task_args, shared_args=(), static_args=None,
                    round_size=None, shared_specs=None, return_timings=False,
                    pad_to_round=False, cache_key=None, on_round=None):
        """Run the stacked kernel on the host's default JAX device.

        Same compiled program as the TPU path minus the mesh sharding, so
        local and distributed results agree bit-for-bit per device type.
        ``round_size`` bounds tasks per compiled round (memory knob),
        exactly as on the device backend. ``pad_to_round`` keeps the
        round shape AT ``round_size`` even when fewer tasks remain
        (padding duplicates the last task; outputs are sliced off in
        ``_run_in_rounds``) — for callers issuing several dispatches
        that must reuse one compiled shape. ``cache_key`` is the
        caller's structural compile-cache key (see
        ``parallel.compile_cache``): per-call kernel closures with the
        same key share one traced/compiled program. ``on_round(start,
        out)`` observes each gathered round (checkpoint journaling).

        Retryable faults (``parallel.faults`` taxonomy) re-dispatch
        from the first unfinished task under the env-configured
        :class:`~skdist_tpu.parallel.faults.RetryPolicy`; inputs are
        immutable host slices, so a retried run is bitwise identical.
        """
        # no donation on the host path: task slices arrive as numpy
        # (uncommitted), which jit cannot donate — requesting it would
        # only emit unusable-donation noise
        fn = _jit_vmapped(kernel, static_args, None, None, cache_key, False)
        self.last_shared_bytes = tree_nbytes(shared_args)
        n_tasks = _leading_dim(task_args)
        if pad_to_round and round_size:
            chunk = round_size
        else:
            chunk = min(n_tasks, round_size or n_tasks)
        timings = [] if return_timings else None
        stats = self.last_round_stats = obs_metrics.new_round_stats(
            tasks=int(n_tasks),
            shared_bytes=int(self.last_shared_bytes or 0),
        )
        import jax

        retry = _RetryState()
        rounds_out = []
        offset = 0
        while offset < n_tasks or not rounds_out:
            sub = (
                jax.tree_util.tree_map(lambda a: a[offset:], task_args)
                if offset else task_args
            )
            cb = (
                None if on_round is None
                else (lambda start, out, _off=offset:
                      on_round(_off + start, out))
            )
            try:
                rounds_out.extend(_run_in_rounds(
                    fn, sub, shared_args, n_tasks - offset, chunk,
                    timings=timings, pipeline=not self.sync_rounds,
                    stats=stats, concat=False, on_round=cb,
                ))
                break
            except _RoundsExhausted as oom:
                # no adaptive retry on host memory; surface the real
                # error — with the flight recorder frozen first (the
                # last rounds' story is the incident's evidence)
                _obs_incident("rounds_exhausted")
                raise oom.cause
            except _RoundFault as rf:
                rounds_out.extend(rf.completed)
                offset += rf.consumed
                retry.admit(rf, offset)
        out = _concat_rounds(rounds_out)
        stats["retries"] = retry.total
        obs_metrics.publish_round_stats(stats)
        return (out, timings) if return_timings else out


class TPUBackend(TaskBackend):
    """Device fan-out over a ``jax.sharding.Mesh``.

    The task axis of every batched kernel is sharded across ``devices``
    along mesh axis ``axis_name``; shared arrays are replicated into each
    device's HBM once per fit (broadcast). With ``t`` tasks and ``d``
    devices each round runs ``ceil(min(t, round_size)/d)*d`` tasks, padded
    tasks carrying zero weight.
    """

    is_device_backend = True

    def __init__(self, devices=None, axis_name="tasks", round_size=None,
                 n_jobs=None, data_axis_size=1, mesh=None,
                 reuse_broadcast=False, compile_cache_dir=None,
                 sync_rounds=None, donate_tasks=True, elastic=None):
        """``data_axis_size`` > 1 builds a 2D ('tasks', 'data') mesh:
        that many devices cooperate on each task with row-sharded shared
        data, while tasks fan out over the remaining factor — the mesh
        for an operand no single device holds (``data_axis_size`` equal
        to the device count: every lane on every device, each device a
        row shard). A row-sharded host array is placed shard by shard,
        each shard in row blocks, the devices' transfers in flight
        together (:func:`_put_mesh_scoped`); round sizing, and the
        ``shared_bytes`` / ``lane_bytes`` / ``logits_bytes`` /
        ``round_bytes_estimate`` it books, count ONE device's share; the
        partitioner keeps a value with an axis of the data's rows
        sharded on it through a solver's loop and reduces partial sums
        only (``collective_ops_compiled`` / ``collective_bytes_compiled``
        of the round stats say what it inserted in the step program);
        a search's refit runs over those shards. The default 1D mesh
        replicates shared data and gives every task one device. On
        either, a search places its X once a fit
        (:meth:`place_shared`), dispatches every bucket over it and
        refits over it. An explicit ``mesh``
        (e.g. from ``parallel.mesh`` helpers) is used as-is; its leading
        axis is the task axis and a 'data' axis, if present, row-shards.

        ``reuse_broadcast=True`` caches device-resident copies of shared
        arrays across fits (keyed by host-array identity + sharding), so
        repeated fits on the same X skip the host→device transfer — the
        analogue of reusing one ``sc.broadcast`` handle, with the same
        contract: mutating a host array after it was broadcast is user
        error (the cached device copy would go stale; reference Spark
        broadcasts behave identically). Off by default.

        ``compile_cache_dir`` places JAX's persistent on-disk
        compilation cache (see ``parallel.compile_cache``), which lets
        repeated service processes skip XLA compilation entirely. It
        yields to ``JAX_COMPILATION_CACHE_DIR`` where that is set, and
        defaults to ``<checkout>/.jax_cache``. ``sync_rounds=True`` (or env
        ``SKDIST_SYNC_ROUNDS=1``) forces the round loop synchronous —
        one round dispatched, gathered, then the next — for debugging;
        the default pipelines rounds (gather of round k overlaps the
        dispatch/compute of round k+1). ``donate_tasks=False`` disables
        donation of per-round task-axis input buffers (donation
        reclaims one round's task-argument HBM for outputs/temps and is
        safe because every round places a fresh slice).

        ``elastic`` opts this backend into elastic execution under
        preemption: ``True`` (or a kwargs dict for
        :class:`~skdist_tpu.parallel.mesh.ElasticMeshManager`, or a
        pre-built manager) makes a PREEMPTED round shrink the mesh to
        the surviving devices, resume from the first unfinished task
        (re-placing shared args through the ordinary placement path),
        and re-grow to the full mesh at the next round boundary once
        capacity returns. Off by default — the non-elastic preemption
        contract (re-place on the SAME mesh) is unchanged.
        """
        import jax
        from jax.sharding import Mesh

        self.round_size = round_size
        self.n_jobs = n_jobs
        self.reuse_broadcast = reuse_broadcast
        self.compile_cache_dir = compile_cache.enable_disk_cache(
            compile_cache_dir
        )
        self.sync_rounds = (
            _env_flag("SKDIST_SYNC_ROUNDS") if sync_rounds is None
            else bool(sync_rounds)
        )
        self.donate_tasks = bool(donate_tasks)
        if mesh is not None:
            self.mesh = mesh
            self.devices = list(mesh.devices.flat)
            self.axis_name = mesh.axis_names[0]
            self.data_axis_size = dict(
                zip(mesh.axis_names, mesh.devices.shape)
            ).get("data", 1)
            self.elastic = self._make_elastic(elastic)
            return
        if devices is None:
            devices = jax.devices()
        self.devices = list(devices)
        self.axis_name = axis_name
        self.data_axis_size = data_axis_size
        if data_axis_size > 1:
            if axis_name != "tasks":
                raise ValueError(
                    "data_axis_size > 1 uses the fixed ('tasks', 'data') "
                    f"mesh; axis_name={axis_name!r} cannot be honoured"
                )
            from .mesh import task_data_mesh

            self.mesh = task_data_mesh(self.devices, data_axis_size)
        else:
            self.mesh = Mesh(np.array(self.devices), (axis_name,))
        self.elastic = self._make_elastic(elastic)

    def _make_elastic(self, spec):
        """Normalise the ``elastic=`` knob: None/False → off; True or
        a kwargs dict → a manager over THIS backend's roster; a
        pre-built :class:`ElasticMeshManager` is adopted as-is."""
        if not spec:
            return None
        from .mesh import ElasticMeshManager

        if isinstance(spec, ElasticMeshManager):
            return spec
        if len(self.mesh.axis_names) > 2:
            raise ValueError(
                "elastic execution supports the standard 1D (tasks,) "
                "and 2D (tasks, data) meshes; got axes "
                f"{self.mesh.axis_names}"
            )
        kwargs = dict(spec) if isinstance(spec, dict) else {}
        return ElasticMeshManager(
            devices=self.devices, axis_name=self.axis_name,
            data_axis_size=self.data_axis_size, **kwargs,
        )

    def _adopt_mesh(self, mesh):
        """Swap in a (shrunken or regrown) elastic mesh: the device
        roster and every placement decision from here on bind to it;
        compiled programs for the new sharding build lazily through
        the ordinary structural-cache path. The data-axis size is
        re-derived from the adopted mesh — a both-axis elastic
        re-layout may have shrunk (or restored) the 'data' axis, and
        every row-sharding decision keys on the CURRENT size."""
        self.mesh = mesh
        self.devices = list(mesh.devices.flat)
        self.data_axis_size = dict(
            zip(mesh.axis_names, mesh.devices.shape)
        ).get("data", 1)

    def elastic_preempted(self):
        """A round classified PREEMPTED: drop cached broadcasts
        (device state is presumed lost) and, when elastic, shrink the
        mesh to the surviving devices. Returns True when the mesh
        CHANGED — callers owning their own dispatch plans (streamed
        drivers) rebuild them; ``batched_map`` re-prepares its plan
        unconditionally, as the non-elastic contract already did."""
        _BCAST_CACHE.clear()
        if self.elastic is None:
            return False
        mesh = self.elastic.on_preempted()
        if mesh is None:
            return False
        self._adopt_mesh(mesh)
        return True

    def elastic_regrow_check(self):
        """Round-boundary half of the elastic contract: while
        degraded, probe for returned capacity and re-grow. Returns
        True when the mesh changed (callers re-place/re-prepare)."""
        if self.elastic is None:
            return False
        mesh = self.elastic.maybe_regrow()
        if mesh is None:
            return False
        _BCAST_CACHE.clear()
        self._adopt_mesh(mesh)
        return True

    def _coordinated_resume(self, local_prefix):
        """Multi-process PREEMPTED: run the epoch agreement
        (``ElasticMeshManager.coordinated_resume``), adopt the
        survivor mesh, and return the agreed resume prefix. Device
        state is presumed lost either way, so cached broadcasts drop
        before the caller's fresh placement pass."""
        _BCAST_CACHE.clear()
        agreed, mesh = self.elastic.coordinated_resume(local_prefix)
        if mesh is not None:
            self._adopt_mesh(mesh)
        return agreed

    @property
    def n_devices(self):
        """Task-axis extent: the number of task slots per round."""
        return self.mesh.shape[self.axis_name]

    @property
    def n_task_slots(self):
        return self.n_devices

    def place_shared(self, shared_args, shared_specs=None):
        """``shared_args`` on the mesh as a dispatch would place them
        (``shared_specs``: :func:`row_sharded_specs`), for a caller
        that holds an operand across dispatches — a placed leaf handed
        to a later dispatch stays where it is — or runs a program of
        its own over it (a search does both with its X: every bucket's
        dispatch and the refit). ``shared_specs`` None, or a mesh
        without a ``data`` axis: a replica a device."""
        return self._resolve_placement(shared_args, shared_specs)[2]

    def _resolve_placement(self, shared_args, shared_specs):
        """Shared sharding/placement logic of the batched plans: resolve
        the task-axis and shared shardings, place the shared args
        (through the opt-in broadcast-reuse cache), and build the
        task-slice ``put``. Returns ``(task_sharding, shared_shardings,
        shared_args_placed, put)``.

        The placement runs under a ``place_shared`` span (``args``:
        ``bytes``, the tree's; ``shards``, the devices that share each
        row-sharded leaf; ``bytes_per_device``, what one device holds of
        it). With tracing ENABLED the span ends only after
        ``jax.block_until_ready`` of the placed tree, so its duration
        is the transfer's and not the enqueue's — the one place tracing
        changes what the host does (untraced, the transfer overlaps the
        host work that follows it)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        task_sharding = NamedSharding(self.mesh, P(self.axis_name))
        rep_sharding = NamedSharding(self.mesh, P())
        if shared_specs is not None and self.data_axis_size > 1:
            # spec tree mirrors shared_args; None leaves mean replicated
            shared_shardings = jax.tree_util.tree_map(
                lambda spec: NamedSharding(
                    self.mesh, spec if isinstance(spec, P) else P()
                ),
                shared_specs,
                is_leaf=lambda x: x is None or isinstance(x, P),
            )
        else:
            shared_shardings = rep_sharding
        tracing = obs_trace.enabled()
        span_args = {"bytes": tree_nbytes(shared_args)} if tracing else None
        with obs_trace.span("place_shared", span_args):
            if isinstance(shared_shardings, NamedSharding):
                # single sharding for the whole tree: leaf-wise put
                # through the reuse cache
                shared_args = jax.tree_util.tree_map(
                    lambda a: _cached_device_put(
                        a, shared_shardings, self.reuse_broadcast
                    ),
                    shared_args,
                )
            else:
                # shardings form a PREFIX tree of shared_args (one
                # sharding per top-level entry; entries may be
                # sub-trees). These skip the reuse cache: a caller
                # that wants a row-sharded leaf to outlive a dispatch
                # places it itself (``place_shared``) and hands it in
                # placed, which the put below leaves where it is
                shared_args = jax.tree_util.tree_map(
                    lambda sh, sub: jax.tree_util.tree_map(
                        lambda a: _put_mesh_scoped(a, sh), sub
                    ),
                    shared_shardings, shared_args,
                    is_leaf=lambda x: isinstance(x, NamedSharding),
                )
            # byte-account what was just placed: packed-CSR leaves count
            # their idx+val bytes, not their logical dense size, and on
            # a mesh with a ``data`` axis a leaf counts the shard ONE
            # device holds of it
            self.last_shared_bytes = tree_nbytes(
                shared_args, per_device=self.data_axis_size > 1)
            if tracing:
                span_args.update(
                    shards=int(self.data_axis_size),
                    bytes_per_device=int(self.last_shared_bytes))
                jax.block_until_ready(shared_args)
        put = lambda t: jax.tree_util.tree_map(
            lambda a: _put_mesh_scoped(a, task_sharding), t
        )
        return task_sharding, shared_shardings, shared_args, put

    def prepare_batched(self, kernel, shared_args=(), static_args=None,
                        shared_specs=None, cache_key=None):
        """Resolve shardings, place shared args (through the opt-in
        broadcast-reuse cache), and build the memoised jit entry ONCE,
        returning a :class:`BatchedPlan` for repeated low-latency
        single-round dispatches. ``batched_map`` itself runs through
        this, so a plan's compiled programs are the same entries the
        offline path uses — a serving flush and a ``batch_predict``
        block of matching shape execute one executable.
        """
        task_sharding, shared_shardings, shared_args, put = (
            self._resolve_placement(shared_args, shared_specs)
        )
        fn = _jit_vmapped(
            kernel, static_args, task_sharding, shared_shardings,
            cache_key, self.donate_tasks,
        )
        return BatchedPlan(fn, shared_args, put,
                           n_task_slots=self.n_devices)

    supports_iterative = True

    def prepare_streamed(self, kernel, block_example=None,
                         static_args=None, cache_key=None,
                         partition_rules=None):
        """Mesh variant of the streamed plan: the task axis shards over
        the task mesh axis exactly like :meth:`prepare_batched`'s, and
        the per-block shared tree row-shards onto the mesh 'data' axis
        when one exists — resolved through the declarative
        partition-rule table (:func:`_block_shardings`;
        ``partition_rules`` overrides the default
        :data:`~skdist_tpu.parallel.mesh.STREAM_BLOCK_RULES`) —
        streamed blocks land on the same axis the resident row-sharded
        path uses, so GSPMD inserts the identical psum of
        gram/gradient partials.

        The returned plan carries a ``rebuild`` hook re-resolving it
        against the backend's CURRENT mesh — the elastic-restart seam
        for the streamed drivers (a both-axis elastic re-layout is
        picked up here, including a shrunken 'data' axis)."""
        self.elastic_regrow_check()

        def resolve(plan):
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            task_sharding = NamedSharding(self.mesh, P(self.axis_name))
            block_shardings = _block_shardings(
                self, block_example, partition_rules
            )
            plan.fn = _jit_vmapped(
                kernel, static_args, task_sharding, block_shardings,
                cache_key, False,
            )

            def put_task(t):
                return jax.tree_util.tree_map(
                    lambda a: _put_mesh_scoped(a, task_sharding), t
                )

            if isinstance(block_shardings, NamedSharding):
                def put_block(t):
                    return jax.tree_util.tree_map(
                        lambda a: _put_mesh_scoped(a, block_shardings), t
                    )
            else:
                def put_block(t):
                    return jax.tree_util.tree_map(
                        _put_mesh_scoped, t, block_shardings
                    )

            plan.put_task = put_task
            plan.put_block = put_block
            plan.n_task_slots = self.n_devices

        plan = StreamPlan(None, None, None, rebuild=resolve)
        resolve(plan)
        return plan

    def prepare_batched_iterative(self, spec, shared_args=(),
                                  static_args=None, shared_specs=None,
                                  cache_key=None):
        """The iterative counterpart of :meth:`prepare_batched`: one
        placement pass, three memoised jit entries (init slice / step
        slice / finalize)."""
        task_sharding, shared_shardings, shared_args, put = (
            self._resolve_placement(shared_args, shared_specs)
        )
        fns = _iterative_jit_entries(
            spec, static_args, task_sharding, shared_shardings, cache_key
        )
        return IterativePlan(*fns, shared_args, put,
                             n_task_slots=self.n_devices,
                             data_shards=self.data_axis_size)

    def batched_map_iterative(self, spec, task_args, shared_args=(),
                              static_args=None, round_size=None,
                              shared_specs=None, return_timings=False,
                              cache_key=None, on_round=None, rung=None):
        """Convergence-compacted execution over the mesh: slice the
        solvers, gather per-lane done flags (flags-only D2H), compact
        survivors into fewer slot-aligned rounds, finalize in original
        task order. An adaptive ``rung`` controller additionally
        scores live carries every K slices and kills the losers
        through the same done-flag path. Multi-process meshes take the
        spec's classic fallback kernel through :meth:`batched_map` —
        the per-slice host compaction decisions would otherwise need
        cross-process agreement at every slice (and the fallback is
        exhaustive: the rung is reset, never applied)."""
        self.elastic_regrow_check()
        n_tasks = _leading_dim(task_args)
        if self._spans_processes():
            return TaskBackend.batched_map_iterative(
                self, spec, task_args, shared_args,
                static_args=static_args, round_size=round_size,
                shared_specs=shared_specs, return_timings=return_timings,
                cache_key=cache_key, on_round=on_round, rung=rung,
            )
        plan = self.prepare_batched_iterative(
            spec, shared_args, static_args, shared_specs, cache_key
        )
        return _dispatch_iterative(
            self, plan, spec, task_args, shared_args, static_args,
            shared_specs, n_tasks,
            _size_iterative_round(self, plan, task_args, n_tasks,
                                  round_size),
            return_timings, cache_key, on_round=on_round, rung=rung,
        )

    def _mesh_min_int(self, value):
        """Minimum of a per-process host integer across THIS mesh's
        processes, as a device computation on the mesh: each process
        feeds its value to its addressable shards of a one-per-device
        global array, and a replicated ``jnp.min`` reduces it. Only
        processes owning devices in the mesh participate — the reason
        this is not ``multihost_utils.process_allgather``, which is a
        job-global collective and deadlocks for subset meshes."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        shape = mesh.devices.shape
        unit = tuple(1 for _ in shape)
        sharding = NamedSharding(mesh, P(*mesh.axis_names))
        shards = [
            jax.device_put(np.full(unit, value, np.int64), d)
            for d in mesh.devices.flat
            if d.process_index == jax.process_index()
        ]
        garr = jax.make_array_from_single_device_arrays(
            shape, sharding, shards
        )
        out = jax.jit(
            jnp.min, out_shardings=NamedSharding(mesh, P())
        )(garr)
        return int(out)

    def _free_device_bytes(self):
        """Free HBM on the first mesh device, or None where the backend
        reports no stats (CPU virtual devices return None). A probe
        failure is logged (once per exception type, then debug-level)
        and counted (``faults`` ``suppressed``), not silently eaten."""
        try:
            stats = self.devices[0].memory_stats()
        except Exception as exc:
            faults.log_suppressed("TPUBackend._free_device_bytes", exc)
            return None
        if not stats or "bytes_limit" not in stats:
            return None
        return stats["bytes_limit"] - stats.get("bytes_in_use", 0)

    # generic host path (non-JAX estimators under a TPU backend still
    # fan out on host threads, like pyspark running a python closure)
    def run_tasks(self, fn, tasks, verbose=0):
        return LocalBackend(n_jobs=self.n_jobs or -1).run_tasks(fn, tasks, verbose)

    def broadcast(self, value):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        leaves = jax.tree_util.tree_leaves(value)
        if leaves and all(hasattr(x, "shape") for x in leaves):
            replicated = NamedSharding(self.mesh, P())
            value = jax.tree_util.tree_map(
                lambda a: _put_mesh_scoped(a, replicated), value
            )
        return _BroadcastHandle(value)

    def _spans_processes(self):
        """Whether THIS mesh's devices live in more than one process —
        the one guard every collective-sensitive decision (chunk
        agreement, OOM resume, round retry) keys on. Deliberately NOT
        ``jax.process_count()``: a host-local mesh inside a larger
        cluster runs independent per-host workloads."""
        return len({d.process_index for d in self.mesh.devices.flat}) > 1

    def batched_map(self, kernel, task_args, shared_args=(), static_args=None,
                    round_size=None, shared_specs=None, return_timings=False,
                    pad_to_round=False, cache_key=None, on_round=None):
        """Stack → shard → compile once → run in rounds → gather.

        ``task_args``: pytree whose leaves have a leading axis of length
        n_tasks. ``shared_args``: pytree placed on the mesh —
        replicated by default, or per-leaf ``PartitionSpec``s via
        ``shared_specs`` (a pytree matching ``shared_args`` with specs
        at row-sharded leaves and None for replicated; only meaningful
        with a 'data' mesh axis). ``round_size`` (per-call, falls back
        to the backend default) bounds tasks per round.
        ``pad_to_round`` keeps the round shape AT ``round_size`` even
        when fewer tasks remain (``_run_in_rounds`` pads by duplicating
        the last task and slices its outputs off) — for callers issuing
        several dispatches that must reuse one compiled shape; the
        proactive/reactive HBM shrinking below still wins over it.
        ``cache_key`` is the caller's structural compile-cache key (see
        ``parallel.compile_cache``): per-call kernel closures with the
        same key share one traced/compiled program across fits.
        ``on_round(start, out)`` observes each gathered round
        (checkpoint journaling). Returns host numpy, leading axis
        n_tasks.

        **Fault handling.** RESOURCE_EXHAUSTED keeps the proactive/
        reactive shrink-and-resume below. A RETRYABLE fault
        (``parallel.faults``: transient XLA runtime error, preemption,
        watchdog) re-dispatches from the first unfinished task at the
        SAME round size, under the env-configured
        :class:`~skdist_tpu.parallel.faults.RetryPolicy`; a preemption
        additionally re-places the shared args (device state is
        presumed lost) through a fresh placement pass. Round inputs are
        immutable host slices, so a retried run is bitwise identical to
        an undisturbed one. Multi-process meshes stay FAIL-LOUD for
        every fault kind — a locally caught exception cannot be
        re-synchronised with peers already inside the next collective —
        with a collective-consistent error message.
        """
        import jax

        # a degraded elastic backend re-grows at dispatch entry too —
        # a fresh fit should start on whatever capacity exists NOW
        self.elastic_regrow_check()
        n_tasks = _leading_dim(task_args)
        d = self.n_devices
        round_size = round_size or self.round_size or n_tasks
        chunk = round_size if pad_to_round else min(n_tasks, round_size)
        chunk = int(math.ceil(chunk / d) * d)

        plan = self.prepare_batched(
            kernel, shared_args, static_args, shared_specs, cache_key
        )
        fn, shared_placed, put = plan.fn, plan.shared, plan.put
        refused0 = faults.snapshot()["rounds_refused"]
        # Proactive round sizing: where the device reports memory
        # stats, AOT-compile the round program and shrink the first
        # round to fit BEFORE dispatch — a device OOM costs a wasted
        # round. The reactive halving below stays as the backstop for
        # workloads whose true footprint beats the linear estimate.
        exec_fn, chunk = _aot_exec_fn(
            fn, shared_placed, task_args, chunk, d,
            self._free_device_bytes(),
        )
        # The guard keys on whether THIS mesh spans processes — NOT on
        # jax.process_count(): a host-local mesh inside a larger
        # cluster runs independent per-host workloads, and injecting a
        # global collective there would deadlock (and wrongly couple
        # unrelated hosts' chunk sizes).
        multiprocess = self._spans_processes()
        if multiprocess:
            # The proactive size is derived from LOCAL free HBM, which
            # can differ per host; a per-host chunk means mismatched
            # round counts and a deadlocked SPMD collective. Agree on
            # the min across the mesh's processes before the first
            # dispatch. The agreement is a device computation ON THIS
            # MESH — not a job-global collective like process_allgather
            # — so a mesh covering a strict subset of the job's
            # processes never blocks on processes that own no device in
            # it (they may be running unrelated work, or nothing).
            chunk = self._mesh_min_int(chunk)
        # HBM-adaptive rounds: a round that exhausts device memory is
        # halved (device-count aligned) and the run RESUMES from the
        # first unfinished task — completed rounds are kept, not
        # recomputed. The analogue of tuning the reference's
        # `partitions` by hand, automated; a new chunk size is a new
        # shape, so jax recompiles transparently.
        timings = [] if return_timings else None
        stats = self.last_round_stats = obs_metrics.new_round_stats(
            tasks=int(n_tasks),
            shared_bytes=int(self.last_shared_bytes or 0),
        )
        retry = _RetryState()
        rounds_out = []
        offset = 0
        salvage_mark = 0  # tasks already credited to elastic salvage
        while offset < n_tasks:
            if self.elastic is not None:
                # production heartbeat probes read these stamps; a
                # manager without a heartbeat sink no-ops
                self.elastic.beat()
            degraded = self.elastic is not None and self.elastic.degraded
            if degraded and self.elastic_regrow_check():
                # capacity returned at a round boundary: re-grow —
                # re-place the shared args on the full mesh and realign
                # the round size to the new device count (compiled
                # programs for the new sharding build lazily)
                d = self.n_devices
                chunk = int(math.ceil(chunk / d) * d)
                plan = self.prepare_batched(
                    kernel, shared_args, static_args, shared_specs,
                    cache_key,
                )
                fn, shared_placed, put = plan.fn, plan.shared, plan.put
                exec_fn, chunk = _aot_exec_fn(
                    fn, shared_placed, task_args, chunk, d, None
                )
                degraded = self.elastic.degraded
            # while degraded, dispatch ONE round per call so every
            # round boundary returns here for the regrow probe — the
            # "re-grow at the next round boundary" half of the elastic
            # contract. Cross-round pipelining is suspended while
            # degraded; it resumes with the full mesh.
            span = min(chunk, n_tasks - offset) if degraded \
                else n_tasks - offset
            sub = (
                jax.tree_util.tree_map(lambda a: a[offset:], task_args)
                if offset else task_args
            )
            cb = (
                None if on_round is None
                else (lambda start, out, _off=offset:
                      on_round(_off + start, out))
            )
            try:
                rounds_out.extend(_run_in_rounds(
                    exec_fn, sub, shared_placed, span, chunk,
                    put=put, timings=timings, concat=False,
                    pipeline=not self.sync_rounds, stats=stats,
                    on_round=cb, drain_on_fault=not multiprocess,
                ))
                offset += span
                continue
            except _RoundsExhausted as oom:
                if multiprocess:
                    # The reactive resume is driven by a LOCALLY caught
                    # exception; other processes saw no failure and are
                    # already inside the next collective — resuming here
                    # with a different round plan would deadlock, not
                    # recover. Fail loudly with the remedy instead.
                    _obs_incident("rounds_exhausted")
                    raise RuntimeError(
                        "batched_map exhausted device memory in a "
                        "multi-process run; the per-process OOM resume "
                        "cannot re-synchronise the SPMD program. Re-run "
                        f"with partitions>={-(-n_tasks // max(chunk // 2, 1))} "
                        "(or a smaller round_size) so every process "
                        "starts with rounds that fit."
                    ) from oom.cause
                rounds_out.extend(oom.completed)
                offset += oom.consumed
                if chunk <= d:
                    _obs_incident("rounds_exhausted")
                    raise oom.cause
                chunk = int(math.ceil(chunk / 2 / d) * d)
                faults.record("rounds_refused")
                warnings.warn(
                    "batched_map round exhausted device memory; resuming "
                    f"at round_size={chunk} (pass partitions="
                    f"{-(-n_tasks // chunk)} to pick this up front)"
                )
            except _RoundFault as rf:
                if multiprocess:
                    if (rf.kind == faults.PREEMPTED
                            and self.elastic is not None
                            and getattr(self.elastic, "can_coordinate",
                                        False)):
                        # Coordinated elastic resume: the survivors
                        # agree on (epoch, gathered-task-prefix,
                        # survivor roster) through the jax.distributed
                        # KV store, the mesh re-forms over the
                        # survivors, and the round loop resumes from
                        # the AGREED prefix — every surviving process
                        # runs this branch symmetrically, so the
                        # re-formed collective stays in lockstep.
                        rounds_out.extend(rf.completed)
                        offset += rf.consumed
                        retry.admit(rf, offset)
                        try:
                            agreed = self._coordinated_resume(offset)
                        except Exception as agree_exc:
                            raise RuntimeError(
                                f"batched_map hit a {rf.kind} fault in "
                                "a multi-process run and the "
                                "coordinated elastic resume itself "
                                f"failed ({agree_exc}); restart the "
                                "job to retry the search (durable "
                                "checkpoints resume past completed "
                                "tasks; see SKDIST_CHECKPOINT_DIR)."
                            ) from rf.cause
                        if agreed < offset:
                            # a peer gathered less: back up to the
                            # agreed prefix (re-running a gathered
                            # round is correct; dispatching rounds a
                            # peer never gathered would desynchronise
                            # the re-formed collective)
                            rounds_out, offset = _truncate_rounds(
                                rounds_out, agreed
                            )
                        faults.record("elastic_tasks_salvaged",
                                      offset - salvage_mark)
                        salvage_mark = offset
                        d = self.n_devices
                        chunk = int(math.ceil(chunk / d) * d)
                        plan = self.prepare_batched(
                            kernel, shared_args, static_args,
                            shared_specs, cache_key,
                        )
                        fn, shared_placed, put = (
                            plan.fn, plan.shared, plan.put
                        )
                        exec_fn, chunk = _aot_exec_fn(
                            fn, shared_placed, task_args, chunk, d, None
                        )
                        faults.record("shared_replacements")
                        multiprocess = self._spans_processes()
                        if multiprocess:
                            chunk = self._mesh_min_int(chunk)
                        continue
                    # Same collective reality as the OOM branch: retry
                    # is single-process only. The message carries no
                    # process-local state (offsets, salvage counts), so
                    # every process that raises prints the same remedy.
                    _obs_incident("multiprocess_round_fault")
                    raise RuntimeError(
                        f"batched_map hit a {rf.kind} fault in a "
                        "multi-process run; round retry cannot "
                        "re-synchronise the SPMD program across "
                        "processes. Restart the job to retry the search "
                        "(durable checkpoints resume past completed "
                        "tasks; see SKDIST_CHECKPOINT_DIR)."
                    ) from rf.cause
                rounds_out.extend(rf.completed)
                offset += rf.consumed
                retry.admit(rf, offset)  # raises rf.cause when spent
                if rf.kind == faults.PREEMPTED:
                    # device state is presumed lost with the preempted
                    # worker: drop cached broadcasts, let an elastic
                    # mesh shrink to the surviving devices, and
                    # re-place the shared args through a fresh
                    # placement pass (the jit entries are host-side
                    # memos and survive; a changed mesh compiles its
                    # own executables lazily). The gathered prefix —
                    # `offset` tasks, the same prefix the checkpoint
                    # journal holds — is NOT re-run: the resume
                    # re-dispatches from the first unfinished task.
                    if self.elastic_preempted():
                        d = self.n_devices
                        chunk = int(math.ceil(chunk / d) * d)
                        # credit only the prefix not already counted by
                        # an earlier shrink in this call — the tasks
                        # the shrunken mesh does NOT re-run
                        faults.record("elastic_tasks_salvaged",
                                      offset - salvage_mark)
                        salvage_mark = offset
                    plan = self.prepare_batched(
                        kernel, shared_args, static_args, shared_specs,
                        cache_key,
                    )
                    fn, shared_placed, put = (
                        plan.fn, plan.shared, plan.put
                    )
                    exec_fn, chunk = _aot_exec_fn(
                        fn, shared_placed, task_args, chunk, d, None
                    )
                    faults.record("shared_replacements")
        out = _concat_rounds(rounds_out)
        stats["retries"] = retry.total
        stats["refused"] = faults.snapshot()["rounds_refused"] - refused0
        obs_metrics.publish_round_stats(stats)
        return (out, timings) if return_timings else out


class BatchedPlan:
    """A pre-resolved batched dispatch: shardings computed, shared args
    device-resident, jit entry memoised (``TaskBackend.prepare_batched``).

    ``batched_map`` builds one per call and runs its round loop over
    it; long-lived callers (the serving engine) hold a plan across many
    calls so the per-dispatch cost is task placement + execution only —
    no shared-data re-placement, no sharding resolution, no round
    scheduling. ``run`` executes a SINGLE round whose task axis length
    is whatever the slice carries (callers shape it to
    ``n_task_slots``); ``prewarm`` AOT-compiles — and, with the disk
    cache enabled, serializes — an explicit task shape with no data, so
    the first live call of that shape never compiles.
    """

    __slots__ = ("fn", "shared", "put", "n_task_slots", "_shared_sig")

    def __init__(self, fn, shared, put, n_task_slots=1):
        self.fn = fn
        self.shared = shared
        self.put = put
        self.n_task_slots = n_task_slots
        self._shared_sig = compile_cache.shape_sig(shared)

    def run(self, task_args):
        """One round: place the task slice, execute the AOT executable
        for its chunk size (a memo hit after prewarm), gather to host
        numpy. The task leading axis must be a multiple of
        ``n_task_slots`` (it shards over the mesh's task axis)."""
        return self.gather(self.run_async(task_args))

    def run_async(self, task_args):
        """Launch one round WITHOUT blocking on results: returns the
        device output tree with an async D2H copy already enqueued
        behind the compute (the same overlap trick as the pipelined
        round loop). Pair with :meth:`gather`; callers overlapping
        launches must bound their in-flight depth themselves."""
        return self.run_async_placed(self.put(task_args))

    def run_async_placed(self, sl):
        """:meth:`run_async` for a task slice ALREADY device-placed —
        the streamed-predict path places blocks on a prefetch worker
        (``BlockFeeder``) and dispatches them here, so the H2D leg
        rides the feed thread instead of the dispatch clock."""
        comp = compile_cache.aot_executable(
            self.fn, self.shared, sl, _leading_dim(sl),
            shared_sig=self._shared_sig,
        )
        dev_out = comp(self.shared, sl)
        _start_host_copy(dev_out)
        return dev_out

    def gather(self, dev_out):
        """Block on a :meth:`run_async` launch: device tree → host
        numpy (multi-process-safe, same leg as the round loop)."""
        return _gather_host(dev_out)

    def prewarm(self, task_like, n_chunk=None):
        """Compile (and disk-export) the program for an explicit task
        shape — pytree of arrays or ``jax.ShapeDtypeStruct``s — without
        dispatching any data. See ``compile_cache.prewarm``."""
        return compile_cache.prewarm(
            self.fn, self.shared, task_like, n_chunk=n_chunk,
            shared_sig=self._shared_sig,
        )


class StreamPlan:
    """A pre-resolved block-streamed dispatch: the jit entry of a
    ``kernel(block, task)`` program whose TASK tree is long-lived
    (placed once, task-axis sharded) while its SHARED tree — one data
    block — is re-placed per block by the feeder
    (:class:`BlockFeeder`). The transpose of :class:`BatchedPlan`:
    there the shared data is resident and tasks stream; here the tasks
    are resident and the data streams. Built by
    :meth:`TaskBackend.prepare_streamed`; driven by the streamed fit/
    predict drivers (``models/streaming.py``).

    The plan is MUTABLE-in-place on elastic backends: after a
    preemption shrinks (or a boundary regrows) the mesh,
    :meth:`rebuild` re-resolves ``fn``/``put_task``/``put_block``
    against the backend's current mesh without changing the plan's
    identity — drivers and feeders that late-bind through the plan
    object (``plan.fn(...)``, ``lambda t: plan.put_block(t)``) pick up
    the new mesh on their next dispatch."""

    __slots__ = ("fn", "put_task", "put_block", "n_task_slots",
                 "_rebuild")

    def __init__(self, fn, put_task, put_block, n_task_slots=1,
                 rebuild=None):
        self.fn = fn
        self.put_task = put_task
        self.put_block = put_block
        self.n_task_slots = n_task_slots
        self._rebuild = rebuild

    def rebuild(self):
        """Re-resolve this plan against the backend's CURRENT mesh
        (elastic shrink/regrow); a no-op on backends without one."""
        if self._rebuild is not None:
            self._rebuild(self)


def _block_shardings(backend, block_example, rules=None):
    """Per-leaf shardings of a streamed block on a mesh backend,
    resolved DECLARATIVELY: a named-axis partition-rule table (regex
    over '/'-joined block-tree paths → ``PartitionSpec``,
    :func:`~skdist_tpu.parallel.mesh.match_partition_rules`) replaces
    the old hand-plumbed leading-dim heuristic. Under the default
    :data:`~skdist_tpu.parallel.mesh.STREAM_BLOCK_RULES` the design
    matrix (dense ``X`` or packed-CSR children) and the per-row
    vectors (``y``/``sw``/``fold``) ride the mesh 'data' axis — the
    streamed analogue of ``row_sharded_specs`` (GSPMD then psums the
    solver contractions over the data axis exactly as in the resident
    row-sharded path) — while per-block scalars (the SGD epoch clock)
    and unmatched leaves replicate. On 1D meshes everything
    replicates."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(backend.mesh, P())
    if getattr(backend, "data_axis_size", 1) <= 1:
        return rep
    if block_example is None:
        # finish-style plans (gram solve, GBDT chooser) take no real
        # block — their placeholder input replicates on any mesh
        return rep
    from .mesh import STREAM_BLOCK_RULES, match_partition_rules

    specs = match_partition_rules(
        STREAM_BLOCK_RULES if rules is None else rules, block_example
    )
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(backend.mesh, spec), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


class BlockFeeder:
    """The double-buffered host→device leg of the streaming data plane.

    Reads blocks (``read(i) -> host tree``) and places them on device
    (``place``) on a background worker, ONE block ahead of the
    consumer, so block ``k+1``'s disk read + H2D transfer hides behind
    block ``k``'s compute — the same depth-2 overlap discipline as the
    pipelined round loop (``_run_in_rounds``), applied to the data axis
    instead of the task axis. ``sync=True`` is the serial-feed debug
    mode (``sync_rounds``' analogue): read + place happen inline in
    :meth:`next`, so the consumer pays the full feed cost on its own
    clock — the baseline the streaming smoke measures overlap against.
    Consumed blocks are dropped as soon as the next is handed out, so
    at most ``depth`` blocks are host+device resident at once.

    :meth:`seek` repositions the cursor — the round-retry contract: a
    transient fault at block ``i`` seeks back to ``i`` and the reader
    is RE-OPENED at exactly that offset (a fresh read; nothing stale
    survives the fault).

    ``stats`` (a dict, typically the backend's ``last_round_stats``)
    accumulates the streamed byte accounting: ``streamed_bytes`` (total
    H2D-fed bytes), ``peak_block_bytes`` (largest single resident
    block), ``blocks_fed``, ``feed_wait_s`` (consumer time blocked on
    the feed — the UNHIDDEN remainder under overlap), ``read_place_s``
    (worker time reading + placing), and ``stream_mode``.
    """

    def __init__(self, read, n_blocks, place, depth=2, sync=False,
                 stats=None):
        self.read = read
        self.n_blocks = int(n_blocks)
        self.place = place
        self.depth = max(2, int(depth))
        self.sync = bool(sync)
        self.stats = stats if stats is not None else {}
        for key, v0 in (
            ("streamed_bytes", 0), ("peak_block_bytes", 0),
            ("blocks_fed", 0), ("feed_wait_s", 0.0),
            ("read_place_s", 0.0),
        ):
            self.stats.setdefault(key, v0)
        self.stats["stream_mode"] = "serial" if self.sync else "pipelined"
        self._cursor = 0
        self._pending = []  # [(idx, Future)]
        self._pool = None

    def _ensure_pool(self):
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="skdist-blockfeed"
            )
        return self._pool

    def _produce(self, i):
        t0 = time.perf_counter()
        with obs_trace.span("block_feed",
                            {"block": int(i)}
                            if obs_trace.enabled() else None):
            host = self.read(i)
            dev = self.place(host)
            nbytes = tree_nbytes(host)
        return dev, nbytes, time.perf_counter() - t0

    def _account(self, nbytes, dt):
        self.stats["streamed_bytes"] += int(nbytes)
        self.stats["peak_block_bytes"] = max(
            self.stats["peak_block_bytes"], int(nbytes)
        )
        self.stats["blocks_fed"] += 1
        self.stats["read_place_s"] += dt

    def seek(self, i):
        """Reposition the cursor to block ``i``; in-flight prefetches
        are discarded (their results never reach the consumer), so the
        next :meth:`next` re-reads from ``i`` — the fault-retry
        offset contract."""
        for _idx, fut in self._pending:
            try:
                fut.cancel() or fut.exception()
            except Exception:  # a failed prefetch is WHY we seek
                pass
        self._pending = []
        self._cursor = int(i)

    def next(self):
        """``(block_index, device_tree)`` for the next block, or None
        past the end. Prefetches the following block before returning,
        so the consumer's compute and the feed overlap."""
        if self.sync:
            if self._cursor >= self.n_blocks:
                return None
            i = self._cursor
            t0 = time.perf_counter()
            dev, nbytes, dt = self._produce(i)
            self.stats["feed_wait_s"] += time.perf_counter() - t0
            self._account(nbytes, dt)
            self._cursor = i + 1
            return i, dev
        pool = self._ensure_pool()
        while (len(self._pending) < self.depth - 1
               and self._cursor + len(self._pending) < self.n_blocks):
            j = self._cursor + len(self._pending)
            self._pending.append((j, pool.submit(self._produce, j)))
        if not self._pending:
            return None
        i, fut = self._pending.pop(0)
        t0 = time.perf_counter()
        dev, nbytes, dt = fut.result()  # a read/place error raises HERE
        self.stats["feed_wait_s"] += time.perf_counter() - t0
        self._account(nbytes, dt)
        self._cursor = i + 1
        # top the prefetch window back up before handing the block out
        if (self._cursor + len(self._pending) < self.n_blocks
                and len(self._pending) < self.depth - 1):
            j = self._cursor + len(self._pending)
            self._pending.append((j, pool.submit(self._produce, j)))
        return i, dev

    def __iter__(self):
        while True:
            item = self.next()
            if item is None:
                return
            yield item

    def close(self):
        self.seek(self.n_blocks)
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# Device-broadcast reuse cache (opt-in via TPUBackend(reuse_broadcast=
# True)): host array identity + sharding -> device-resident replica.
# Entries validate the weakref target IS the original host array, so a
# recycled id() can never serve a stale buffer; a weakref finalizer
# evicts the entry (freeing the pinned device HBM) as soon as the host
# array is collected, and a FIFO bound caps pinned HBM regardless.
_BCAST_CACHE = {}
# must exceed the number of >= _BCAST_MIN_BYTES leaves ONE fit places
# (a CV fit's shared tree has 5: X, y, sw, train/test masks; a packed
# X a few dozen) or a fit's own placement pass evicts its X before the
# NEXT fit on the same host array can hit it; eviction is LRU (hits
# refresh recency) so long-lived X outlives transient per-fit leaves.
# (Within a fit nothing relies on a hit: a search holds its placed X
# and hands it to every dispatch and to the refit.)
_BCAST_MAX = 16
_BCAST_MIN_BYTES = 1 << 20  # caching tiny arrays is pure overhead
_BCAST_HITS = 0  # diagnostics + test observability


#: a host array at least this large reaches the device in row blocks:
#: on the v5e ONE transfer of 6.27 GB took 33 s (17 to 21 s for other
#: widths over 4 GiB) where 3.2 GB take 0.4 s — a fit's placement and
#: its refit's were 64 of its 110 seconds (PERF.md, PR 32)
_BLOCK_PUT_BYTES = 1 << 32


_WRITE_ROWS = None


def _write_rows():
    """The jitted in-place write of a row block (made once: jax is
    imported where it is first needed, as everywhere in this module)."""
    global _WRITE_ROWS
    if _WRITE_ROWS is None:
        import jax

        _WRITE_ROWS = jax.jit(
            lambda whole, block, at: jax.lax.dynamic_update_slice_in_dim(
                whole, block, at, axis=0),
            donate_argnums=0)
    return _WRITE_ROWS


def _row_blocks(x, nbytes=None):
    """``(at, rows)`` of the row blocks of about ``nbytes`` a host array
    of :data:`_BLOCK_PUT_BYTES` or more goes to a device in (a quarter
    of that bound, 1 GiB, where none is given); the last is cut over
    the end of the one before it."""
    nbytes = nbytes or _BLOCK_PUT_BYTES // 4
    rows = max(1, len(x) // -(-x.nbytes // nbytes))
    return [(min(at, len(x) - rows), rows) for at in range(0, len(x), rows)]


def _goes_in_blocks(x):
    return (isinstance(x, np.ndarray) and x.nbytes >= _BLOCK_PUT_BYTES
            and x.ndim >= 1 and len(x) >= 2)


def put_host_array(x, sharding=None):
    """``jax.device_put(x, sharding)`` (uncommitted on the default
    device where ``sharding`` is None) for ONE device or a replica on
    each — a host array of :data:`_BLOCK_PUT_BYTES` or more in row
    blocks of a quarter of that, each written into the whole on the
    device and waited for, so that beside the whole only one block is
    ever held. (A
    row-sharded array goes the same way shard by shard:
    :func:`_put_row_shards`.) A device array is never cut: it goes as
    ``jax.device_put`` moves it — where it already lies, as the same
    buffer; onto other devices, device to device (a bucketed X's
    head, which its pack builds on the device)."""
    import jax

    def put(a):
        return (jax.device_put(a) if sharding is None
                else jax.device_put(a, sharding))

    if not _goes_in_blocks(x):
        return put(x)
    import jax.numpy as jnp

    if sharding is None:
        whole = jnp.zeros(x.shape, x.dtype)
    else:
        whole = jax.make_array_from_single_device_arrays(
            x.shape, sharding, [
                _zeros_on(d, x.shape, x.dtype) for d in
                sharding.addressable_devices_indices_map(x.shape)])
    for at, rows in _row_blocks(x):
        whole = _write_rows()(whole, put(x[at:at + rows]), at)
        # waited for, block by block: a block holds its device buffer
        # from the moment it is enqueued, so a loop that runs ahead has
        # EVERY block in flight beside the whole — seven of 1.05 GB
        # beside 6.27: a peak of 13.59 GB where this reads 7.32, at the
        # same 0.73-0.80 s (PERF.md, PR 36)
        jax.block_until_ready(whole)
    return whole


def _zeros_on(device, shape, dtype):
    """Zeros made ON ``device``: ``jnp.zeros(..., device=)`` fills the
    default device and copies from there, a second whole (or shard) on
    device 0 (its peak 12.77 GB for a shard of 6.35: PERF.md, PR 35)."""
    import jax
    import jax.numpy as jnp

    with jax.default_device(device):
        return jax.device_put(jnp.zeros(shape, dtype), device)


#: a SHARD's row blocks are a 64th of :data:`_BLOCK_PUT_BYTES`
#: (64 MiB), sixteen of them (1 GiB) in flight a device. On the
#: four-chip v5e host the size of ONE transfer sets the rate: the
#: cell's 25.4 GB over four devices took 50.7 s in blocks of 1 GiB
#: (half of a fit) and 0.9 to 2.2 s in blocks of 64 MiB; 8.6 GB took
#: 6.46 s in 1 GiB, 4.23 s in 256 MiB, 0.65 s in 64 MiB (PERF.md,
#: PR 35). One chip's machine takes 1 GiB blocks at 9 GB/s, so
#: :func:`put_host_array` keeps them.
_SHARD_BLOCK_SHARE = 64
_SHARD_BLOCKS_IN_FLIGHT = 16


def _put_row_shards(x, sharding):
    """A host array onto a sharding that cuts it (fully addressable):
    ``jax.device_put(x, sharding)``, or, where a device's shard is
    :data:`_BLOCK_PUT_BYTES` or more, each shard in row blocks (of a
    :data:`_SHARD_BLOCK_SHARE`-th of that bound), written into that
    device's shard in place as :func:`put_host_array` writes a whole. A
    shard of a row-sharded array is a contiguous row slice of ``x`` and
    so are its blocks: views, no host copy. The blocks go block-major
    — every device's first, then every device's second — so the
    devices' transfers are in flight together and a shard never waits
    for another's; every :data:`_SHARD_BLOCKS_IN_FLIGHT` blocks what
    was enqueued is waited for, so that beside a shard a device never
    holds more than that many blocks (nor the host as many staged),
    and the last are waited for too: what round sizing then reads of a
    device's free memory is the placed shard and no block in flight."""
    import jax

    parts = [(d, x[idx]) for d, idx in
             sharding.addressable_devices_indices_map(x.shape).items()]
    if not any(_goes_in_blocks(part) for _, part in parts):
        return jax.device_put(x, sharding)
    import jax.numpy as jnp

    wholes = [_zeros_on(d, part.shape, part.dtype) for d, part in parts]
    blocks = [_row_blocks(part, _BLOCK_PUT_BYTES // _SHARD_BLOCK_SHARE)
              for _, part in parts]
    for b in range(max(len(bl) for bl in blocks)):
        for i, (d, part) in enumerate(parts):
            if b < len(blocks[i]):
                at, rows = blocks[i][b]
                wholes[i] = _write_rows()(
                    wholes[i], jax.device_put(part[at:at + rows], d), at)
        if (b + 1) % _SHARD_BLOCKS_IN_FLIGHT == 0:
            jax.block_until_ready(wholes)
    jax.block_until_ready(wholes)
    return jax.make_array_from_single_device_arrays(
        x.shape, sharding, wholes)


def _put_mesh_scoped(x, sharding):
    """``device_put`` that never joins a JOB-GLOBAL collective.

    ``jax.device_put`` of a host value to a sharding that is not fully
    addressable (a mesh spanning processes) runs
    ``multihost_utils.assert_equal`` — a collective over EVERY process
    in the job. For a mesh covering a strict subset of the job's
    processes that deadlocks (or crashes the transport) against
    non-members that never join — the exact failure class
    ``_mesh_min_int`` exists to avoid for chunk agreement. Instead,
    each process places its OWN addressable shards and assembles the
    global array (collective-free); the SPMD contract that every
    participating process passes the same host value is assumed, as it
    already is for the round loop itself. Fully-addressable shardings
    (single-process) take the plain path: a replica on each device
    through :func:`put_host_array`, a host array cut across devices
    through :func:`_put_row_shards` — both in row blocks where a
    device's part is several GiB.
    """
    import jax

    if getattr(sharding, "is_fully_addressable", True):
        if getattr(sharding, "is_fully_replicated", False):
            return put_host_array(x, sharding)
        if _goes_in_blocks(x):
            return _put_row_shards(x, sharding)
        return jax.device_put(x, sharding)
    if getattr(x, "is_fully_addressable", True) is False:
        # already a global (multi-process) array: jax reshards it on
        # device without consulting a host value, so there is no
        # equality collective to avoid — and np.asarray on it would
        # raise rather than fetch non-addressable shards
        return jax.device_put(x, sharding)
    # host value (or a local device array, at the price of one D2H
    # copy): assemble from this process's shards
    x = np.asarray(x)
    shards = [
        jax.device_put(x[idx], d)
        for d, idx in
        sharding.addressable_devices_indices_map(x.shape).items()
    ]
    return jax.make_array_from_single_device_arrays(
        x.shape, sharding, shards
    )


def _cached_device_put(leaf, sharding, enabled):
    import weakref

    global _BCAST_HITS
    if not enabled or not isinstance(leaf, np.ndarray) \
            or leaf.nbytes < _BCAST_MIN_BYTES:
        return _put_mesh_scoped(leaf, sharding)
    key = (id(leaf), sharding)
    ent = _BCAST_CACHE.get(key)
    if ent is not None:
        ref, dev = ent
        if ref() is leaf:
            _BCAST_HITS += 1
            if _BCAST_CACHE.pop(key, None) is not None:  # LRU refresh
                _BCAST_CACHE[key] = ent
            return dev
        _BCAST_CACHE.pop(key, None)  # id() recycled; never serve stale
    dev = _put_mesh_scoped(leaf, sharding)
    _BCAST_CACHE[key] = (
        weakref.ref(leaf, lambda _ref: _BCAST_CACHE.pop(key, None)),
        dev,
    )
    while len(_BCAST_CACHE) > _BCAST_MAX:
        try:
            _BCAST_CACHE.pop(next(iter(_BCAST_CACHE)))
        except (KeyError, StopIteration):  # concurrent eviction
            break
    return dev


def _obs_incident(reason):
    """Freeze the flight recorder to a timestamped incident file right
    before a fail-loud raise (best-effort + throttled — see
    ``obs.flightrec``)."""
    from ..obs import flightrec

    flightrec.dump_incident(reason)


class _RoundsExhausted(Exception):
    """Internal: a round hit RESOURCE_EXHAUSTED. Carries the rounds that
    DID complete (host numpy) and how many tasks they cover, so the
    caller can resume from the first unfinished task at a smaller
    round size."""

    def __init__(self, completed, consumed, cause):
        super().__init__(str(cause))
        self.completed = completed
        self.consumed = consumed
        self.cause = cause


class _RoundFault(Exception):
    """Internal: a round failed with a RETRYABLE fault (transient XLA
    runtime error, preemption, watchdog — ``faults.classify``). Same
    salvage contract as :class:`_RoundsExhausted`: ``completed`` is a
    contiguous task-prefix of gathered rounds covering ``consumed``
    tasks, so the caller re-dispatches from the first unfinished task —
    at the SAME round size (the fault was not a memory verdict)."""

    def __init__(self, completed, consumed, cause, kind):
        super().__init__(str(cause))
        self.completed = completed
        self.consumed = consumed
        self.cause = cause
        self.kind = kind


class _RetryState:
    """Consecutive-attempt accounting for the round-retry loops: the
    budget is per ROUND (the counter resets whenever the task offset
    advances — progress proves the fault really was transient), so a
    long search tolerating one hiccup per round is not capped at
    ``max_retries`` faults total."""

    __slots__ = ("policy", "attempts", "last_offset", "total")

    def __init__(self, policy=None):
        self.policy = policy or faults.RetryPolicy()
        self.attempts = 0
        self.last_offset = -1
        self.total = 0

    def admit(self, rf, offset):
        """Admit one more re-dispatch after ``rf`` salvaged up to task
        ``offset`` — or raise ``rf.cause`` when the per-round budget is
        spent. Sleeps the policy backoff before returning."""
        if offset != self.last_offset:
            self.attempts = 0
            self.last_offset = offset
        self.attempts += 1
        if self.attempts > self.policy.max_retries:
            faults.record("retries_exhausted")
            raise rf.cause
        self.total += 1
        faults.record("rounds_retried")
        warnings.warn(
            f"batched round hit a {rf.kind} fault "
            f"({type(rf.cause).__name__}); re-dispatching from task "
            f"{offset} (attempt {self.attempts}/{self.policy.max_retries}, "
            f"backoff {self.policy.delay_s(self.attempts) * 1e3:.0f} ms)"
        )
        self.policy.backoff(self.attempts)


def _gather_host(tree):
    """collect(): device outputs → host numpy.

    Single-process: plain ``device_get``. Multi-process SPMD: outputs
    sharded over a mesh that spans processes are not fully
    addressable; each leaf is replicated BY A COLLECTIVE ON ITS OWN
    MESH (a jit identity with replicated out_shardings — the allgather
    rides ICI/DCN among the mesh's processes only) and then read from
    a local replica. NOT ``process_allgather``, which is a job-global
    collective: for a mesh covering a strict subset of the job's
    processes it would block on (or crash against) processes that own
    no device in the mesh — the same deadlock class the chunk
    agreement (``_mesh_min_int``) and placement (``_put_mesh_scoped``)
    avoid. Safe because the round loop is replicated SPMD across the
    mesh's processes: every member gathers the same leaves in the same
    order. This is the DCN leg of the reference's ``collect()``: every
    host ends with the full result, which is what the driver-side
    cv_results_ assembly expects.
    """
    import jax

    if jax.process_count() == 1:
        return jax.device_get(tree)
    from jax.sharding import NamedSharding, PartitionSpec as P

    def one(x):
        if getattr(x, "is_fully_addressable", True):
            return jax.device_get(x)
        replicate = _jit_replicate(NamedSharding(x.sharding.mesh, P()))
        return np.asarray(replicate(x).addressable_data(0))

    return jax.tree_util.tree_map(one, tree)


_REPLICATE_CACHE = {}


def _jit_replicate(replicated_sharding):
    """Identity jit with replicated out_shardings, memoised per
    sharding — the mesh-scoped allgather used by ``_gather_host``."""
    import jax

    fn = _REPLICATE_CACHE.get(replicated_sharding)
    if fn is None:
        fn = jax.jit(lambda v: v, out_shardings=replicated_sharding)
        _REPLICATE_CACHE[replicated_sharding] = fn
    return fn


def _concat_rounds(outs):
    import jax

    if len(outs) == 1:
        return outs[0]
    return jax.tree_util.tree_map(lambda *xs: np.concatenate(xs, axis=0), *outs)


def _truncate_rounds(rounds_out, keep):
    """Trim a list of gathered round outputs to the first ``keep``
    tasks (coordinated resume: a peer's agreed prefix was shorter than
    this process gathered). Returns ``(rounds, kept)``."""
    import jax

    out, have = [], 0
    for r in rounds_out:
        n = _leading_dim(r)
        if have + n <= keep:
            out.append(r)
            have += n
            if have == keep:
                break
            continue
        take = keep - have
        if take > 0:
            out.append(jax.tree_util.tree_map(lambda a: a[:take], r))
            have += take
        break
    return out, have


#: at most this many rounds' args/outputs device-resident at once (one
#: executing + one queued behind it keeps dispatch/compute overlap)
_MAX_ROUNDS_IN_FLIGHT = 2


def _start_host_copy(dev_out):
    """Best-effort async D2H on a dispatched round's outputs: the copy
    enqueues behind the round's compute on the device stream while the
    host moves on to slicing/placing/dispatching the NEXT round — by the
    time the blocking gather reaches these arrays the bytes are already
    (or nearly) on host. Non-addressable leaves (multi-process meshes)
    are skipped; they take ``_gather_host``'s allgather leg. Errors are
    logged and absorbed (``faults.log_suppressed`` at debug level): a
    poisoned async computation re-surfaces at the blocking gather,
    where the OOM-resume/retry machinery classifies it — this early
    echo must not pre-empt that handling."""
    import jax

    try:
        for leaf in jax.tree_util.tree_leaves(dev_out):
            if getattr(leaf, "is_fully_addressable", True):
                leaf.copy_to_host_async()
    except Exception as exc:
        faults.log_suppressed("_start_host_copy", exc,
                              level=logging.DEBUG)


def _run_in_rounds(fn, task_args, shared_args, n_tasks, chunk, put=None,
                   timings=None, concat=True, pipeline=True, stats=None,
                   on_round=None, drain_on_fault=True):
    """Shared round loop: slice task axis, pad the tail round to the
    fixed chunk shape (padding duplicates the last task; its outputs are
    sliced off), run, gather to host numpy, concatenate (or return the
    per-round list with ``concat=False``).

    ``pipeline=True`` (the default) double-buffers the rounds: dispatch
    depth is BOUNDED at :data:`_MAX_ROUNDS_IN_FLIGHT`, and each
    dispatched round's outputs immediately start an async D2H copy
    (:func:`_start_host_copy`), so round k's gather rides the device
    stream behind round k+1's dispatch instead of serialising after it.
    The bound guarantees at most two rounds' task args + outputs are
    device-resident at once. (Dispatching ALL rounds up front made the
    aggregate footprint grow with the round count, which defeated the
    proactive HBM sizing in exactly the shrunk-chunk case it exists for
    — round-2 advisor.) ``pipeline=False`` (the backends'
    ``sync_rounds`` debug flag) forces one round at a time: dispatch,
    block on its gather, then dispatch the next. Both modes execute the
    same compiled program on the same inputs, so gathered outputs are
    bitwise identical.

    ``timings``: optional list; appends ``(round_wall_s, n_tasks_kept)``
    per round — measured gather-to-gather so the walls are
    non-overlapping and sum to the call's total despite pipelining.

    ``stats``: optional dict; accumulates scheduler observability —
    ``rounds``, ``dispatch_s`` (host time spent slicing/placing/
    enqueueing), ``gather_wait_s`` (host time BLOCKED on device
    results; with pipelining this is the unoverlapped remainder),
    ``mode``.

    ``on_round``: optional callback ``on_round(start, out)`` invoked as
    each round's outputs land on host (FIFO, so ``start`` — the round's
    first task index relative to ``task_args`` — is contiguous with the
    previous call). The durable-checkpoint layer journals completed
    rounds through this; a round lost to a fault never fires it, and a
    retried round fires it exactly once, on the attempt that gathered.

    A RESOURCE_EXHAUSTED failure raises :class:`_RoundsExhausted`
    carrying the successfully gathered rounds; a retryable fault
    (``faults.classify``) raises :class:`_RoundFault` with the same
    salvage contract. Other exceptions propagate untouched.
    """
    import jax

    depth = _MAX_ROUNDS_IN_FLIGHT if pipeline else 1
    if stats is not None:
        stats["mode"] = "pipelined" if pipeline else "synchronous"
        stats.setdefault("rounds", 0)
        stats.setdefault("dispatch_s", 0.0)
        stats.setdefault("gather_wait_s", 0.0)
    t_prev = time.perf_counter() if timings is not None else None
    outs = []
    consumed = 0
    pending = []
    in_gather = False
    injector = faults.active_injector()

    def _gather_oldest():
        nonlocal t_prev, consumed, in_gather
        dev_out, keep, pad, inj_round = pending.pop(0)
        in_gather = True
        t_g = time.perf_counter() if stats is not None else None
        with obs_trace.span("round_gather"):
            out = _gather_host(dev_out)
        if stats is not None:
            stats["gather_wait_s"] += time.perf_counter() - t_g
        in_gather = False
        if timings is not None:
            now = time.perf_counter()
            timings.append((now - t_prev, keep))
            t_prev = now
        if pad:
            out = jax.tree_util.tree_map(lambda a: a[:keep], out)
        if inj_round is not None:
            # deterministic NaN-lane poisoning rides the gather path so
            # injected divergence looks exactly like a diverged kernel
            out = injector.transform_output(inj_round, out)
        if on_round is not None:
            on_round(consumed, out)
        outs.append(out)
        consumed += keep

    try:
        for start in range(0, n_tasks, chunk):
            if not pipeline:
                # strict synchronous debug mode: the previous round is
                # fully on host before ANY host work for the next starts
                while pending:
                    _gather_oldest()
            t_d = time.perf_counter() if stats is not None else None
            stop = min(start + chunk, n_tasks)
            sl = jax.tree_util.tree_map(lambda a: a[start:stop], task_args)
            pad = chunk - (stop - start)
            if pad:
                sl = jax.tree_util.tree_map(
                    lambda a: np.concatenate(
                        [a, np.repeat(a[-1:], pad, axis=0)]
                    ),
                    sl,
                )
            if put is not None:
                sl = put(sl)
            if stats is not None:
                # pause the dispatch clock over the blocked gather below
                # — its wall belongs to gather_wait_s alone, and the
                # dispatch_s / gather_wait_s split is what bench's
                # `overlap` aux reports
                stats["dispatch_s"] += time.perf_counter() - t_d
            while len(pending) >= depth:
                _gather_oldest()
            t_d = time.perf_counter() if stats is not None else None
            # fault-injection seam: a planned transient/OOM/hang fires
            # HERE, where a real device dispatch would fail; the
            # returned ordinal tags this round for output poisoning
            inj_round = (
                injector.round_dispatched() if injector is not None
                else None
            )
            with obs_trace.span("round_dispatch"):
                dev_out = fn(shared_args, sl)
            pending.append((dev_out, stop - start, pad, inj_round))
            if stats is not None:
                stats["rounds"] += 1
                stats["dispatch_s"] += time.perf_counter() - t_d
            if pipeline:
                _start_host_copy(dev_out)
        while pending:
            _gather_oldest()
    except Exception as exc:
        kind = faults.classify(exc)
        if kind == faults.OOM:
            def wrap():
                return _RoundsExhausted(outs, consumed, exc)
        elif faults.is_retryable(kind):
            def wrap():
                return _RoundFault(outs, consumed, exc, kind)
        else:
            raise
        # .completed is consumed by the retry/resume loops as a
        # CONTIGUOUS task prefix (offset += consumed), so what may be
        # salvaged depends on where the failure surfaced:
        if in_gather or not drain_on_fault:
            # inside _gather_oldest (the normal case under async
            # dispatch): the failed round was already popped, so every
            # round still pending comes AFTER the gap — gathering it
            # into outs would silently misalign later outputs to
            # earlier tasks (round-3 advisor, high). Drop them; the
            # resume re-runs from the first missing task.
            # drain_on_fault=False is the MULTI-PROCESS dispatch-fault
            # contract: on an SPMD mesh the gather of an in-flight
            # round is a collective, and after a fault (a preempted
            # peer being the canonical case) entering a fresh
            # collective can wedge this process forever against a
            # peer that will never join it — the salvage must stop at
            # what is ALREADY on host, and the coordinated-resume
            # prefix agreement accounts for the dropped rounds.
            pending.clear()
        else:
            # at dispatch: everything pending precedes the failed
            # round — gather it to extend the contiguous prefix,
            # stopping at the first round that itself fails. Only
            # faults of the taxonomy are absorbed into the salvage
            # (they re-surface on the resumed rounds if persistent); a
            # FATAL drain error outranks the resume and propagates.
            while pending:
                try:
                    _gather_oldest()
                except Exception as drain_exc:
                    pending.clear()
                    if faults.classify(drain_exc) == faults.FATAL:
                        raise
                    faults.log_suppressed(
                        "_run_in_rounds.drain", drain_exc
                    )
                    break
        raise wrap() from None
    if not concat:
        return outs
    return _concat_rounds(outs)


def _leading_dim(task_args):
    import jax

    leaves = jax.tree_util.tree_leaves(task_args)
    if not leaves:
        raise ValueError("batched_map needs at least one task-axis array")
    return leaves[0].shape[0]


# ---------------------------------------------------------------------------
# convergence-compacted iterative dispatch
# ---------------------------------------------------------------------------

class _LiveRound:
    """One chunk-shaped round of the compacted slice loop: the original
    task ids it carries (``len(idx) <= chunk``; trailing lanes are
    padding), its host task slice (placed once — ``dev_task`` caches
    the device copy across slices, safe because the iterative jit
    entries never donate), and its carry — device-resident between
    slices, host-resident only across a compaction event. ``done`` is
    the flags of ``dev_carry`` once read; ``ahead`` is the carry of a
    slice enqueued on ``dev_carry`` before those flags were read (the
    look-ahead of a lone round), which becomes ``dev_carry`` when the
    next slice is enqueued on it."""

    __slots__ = ("idx", "task_sl", "dev_task", "dev_carry", "host_carry",
                 "done", "ahead")

    def __init__(self, idx, task_sl):
        self.idx = idx
        self.task_sl = task_sl
        self.dev_task = None
        self.dev_carry = None
        self.host_carry = None
        self.done = None
        self.ahead = None


def _pad_tail(tree, pad):
    import jax

    if not pad:
        return tree
    return jax.tree_util.tree_map(
        lambda a: np.concatenate(
            [np.asarray(a), np.repeat(np.asarray(a)[-1:], pad, axis=0)]
        ),
        tree,
    )


def _dispatch_iterative(backend, plan, spec, task_args, shared_args,
                        static_args, shared_specs, n_tasks, sizing,
                        return_timings, cache_key, on_round=None,
                        rung=None):
    """Run the compacted loop at ``sizing`` (the ``(chunk, chunk_basis,
    lanes_fit)`` of :func:`_size_iterative_round`, the last two booked
    into the stats as they are) with two safety nets. A
    RESOURCE_EXHAUSTED anywhere (a compacted round's carries do not fit,
    or the finalize pass trips the round loop's OOM machinery) downgrades
    to a plain ``batched_map`` of the spec's fallback kernel at the same
    round size — correctness never depends on the slice loop. A
    RETRYABLE fault (``parallel.faults`` taxonomy) re-runs the whole
    compacted dispatch under the env-configured RetryPolicy — carries
    live on device between slices, so a mid-slice fault has no durable
    prefix to salvage the way the classic round loop does; a full
    re-run is the round-granular retry at this path's granularity, and
    it is bitwise identical (the slice loop is deterministic). When the
    budget is spent, the classic fallback kernel (which retries per
    round) is the last resort before failing loud."""
    chunk, chunk_basis, lanes_fit = sizing
    resident, transient, fixed, rows = _lane_footprint(plan, task_args)
    shared_bytes = int(backend.last_shared_bytes or 0)
    # every byte count is of what ONE device holds: on a mesh with a
    # ``data`` axis a row shard of the shared operands and of every
    # value with an axis of the data's rows (``data_shards`` devices
    # share them)
    stats = backend.last_round_stats = obs_metrics.new_round_stats(
        tasks=int(n_tasks), shared_bytes=shared_bytes,
        lane_bytes=int(resident + transient), logits_bytes=int(rows),
        round_bytes_estimate=int(
            shared_bytes + fixed + chunk * (resident + transient)),
        chunk_basis=chunk_basis, lanes_fit=lanes_fit,
        data_shards=int(plan.data_shards),
    )
    # where device memory set the size, one round's carry is resident
    live_rounds = 1 if chunk_basis == "memory" else None
    t0 = time.perf_counter()
    retry = _RetryState()
    refused0 = faults.snapshot()["rounds_refused"]
    while True:
        try:
            if rung is not None:
                # a retried attempt restarts the carries from scratch:
                # the rung history (and any kills decided against the
                # aborted trajectory) must restart with them
                rung.reset()
            out = _run_compacted(
                plan, spec, task_args, n_tasks, chunk, stats,
                pipeline=not backend.sync_rounds, on_round=on_round,
                rung=rung, live_rounds=live_rounds, lanes_fit=lanes_fit,
            )
            stats["retries"] = retry.total
            obs_metrics.publish_round_stats(stats)
            break
        except Exception as exc:
            if isinstance(exc, (_RoundsExhausted, _RoundFault)):
                cause = exc.cause
                kind = (
                    exc.kind if isinstance(exc, _RoundFault)
                    else faults.OOM
                )
            else:
                cause = exc
                kind = faults.classify(exc)
            if faults.is_retryable(kind):
                try:
                    retry.admit(
                        _RoundFault([], 0, cause, kind), 0
                    )
                    if kind == faults.PREEMPTED:
                        # same contract as the classic path: device
                        # state (placed shared args, cached broadcasts)
                        # is presumed lost with the preempted worker —
                        # retrying against the old plan's buffers would
                        # burn the whole budget on dead state. An
                        # elastic backend additionally shrinks its mesh
                        # to the survivors here (the divisor rule keeps
                        # `chunk` slot-aligned on the shrunken mesh, so
                        # the compacted rounds re-run unchanged).
                        backend.elastic_preempted()
                        plan = backend.prepare_batched_iterative(
                            spec, shared_args, static_args,
                            shared_specs, cache_key,
                        )
                        faults.record("shared_replacements")
                    continue
                except Exception:
                    # budget spent: the classic fallback below is the
                    # last resort before surfacing the fault
                    if spec.fallback is None:
                        raise cause from None
                    warnings.warn(
                        f"compacted iterative dispatch kept hitting "
                        f"{kind} faults; falling back to the classic "
                        f"batched path at round_size={chunk}"
                    )
            elif kind == faults.OOM:
                if spec.fallback is None:
                    raise cause
                faults.record("rounds_refused")
                warnings.warn(
                    "compacted iterative dispatch exhausted device "
                    "memory; falling back to the classic batched path "
                    f"at round_size={chunk}"
                )
            else:
                raise
            if rung is not None:
                # the classic fallback runs every lane to completion;
                # kills decided against the aborted compacted attempt
                # must not error-score lanes that will now finish — and
                # the caller must learn no adaptive race happened
                rung.deactivate()
            # the abandoned compacted attempt still publishes what it
            # accumulated (retries that forced this downgrade included)
            # — the fallback's own dispatch publishes separately under
            # its own path label. "rounds" is normally summed on clean
            # slice-loop exit; fold the partial attempt's here.
            stats["retries"] = retry.total
            stats["rounds"] = int(sum(
                stats.get("rounds_per_slice", []) or [0]
            ))
            obs_metrics.publish_round_stats(stats)
            out = backend.batched_map(
                spec.fallback, task_args, shared_args,
                static_args=static_args, round_size=chunk,
                shared_specs=shared_specs, return_timings=return_timings,
                cache_key=spec.fallback_cache_key or cache_key,
                on_round=on_round,
            )
            # the fallback's stats are the dispatch's: what the
            # compacted attempt had refused counts with its own
            backend.last_round_stats["refused"] = (
                faults.snapshot()["rounds_refused"] - refused0)
            return out
    if return_timings:
        # one pseudo-round covering the whole call: per-task wall is a
        # uniform smear (slices interleave tasks, so a per-round
        # attribution would be fiction); the scheduler detail lives in
        # last_round_stats instead
        return out, [(time.perf_counter() - t0, n_tasks)]
    return out


def _flags_only_gather(leaf):
    """D2H of ONE carry leaf (the done flags) — the only per-slice
    transfer of the compacted loop's decision path. Always a real copy
    (``np.array``): on the CPU backend ``device_get`` can return a
    zero-copy view of the device buffer, and the loop must never hold a
    view across the slice boundary that recycles that buffer."""
    import jax

    if getattr(leaf, "is_fully_addressable", True):
        return np.array(jax.device_get(leaf))
    return np.array(_gather_host(leaf))


def _run_compacted(plan, spec, task_args, n_tasks, chunk, stats,
                   pipeline=True, on_round=None, rung=None,
                   live_rounds=None, lanes_fit=None):
    """The convergence-compacted slice loop.

    Phase 1 (iterate): partition the task axis into chunk-shaped rounds
    and dispatch the init-slice program over each; per slice thereafter,
    gather ONLY each round's ``done`` flags (flags-only D2H — carries
    stay device-resident between slices), retire rounds whose lanes all
    finished, and, when the survivor count frees at least one round,
    COMPACT the still-running lanes into fewer dense rounds (the one
    point where surviving carries cross the host). Retired lanes store
    only their ``finalize_keys`` carry leaves.

    With an adaptive ``rung`` controller (and a spec that carries a
    rung-score kernel), every ``rung.every`` slices the live rounds'
    carries are additionally scored ON DEVICE by the fourth jit entry
    — one ``(chunk,)`` score vector per round is the only extra D2H —
    and the controller's losers are marked done, so they retire
    through the very same done-flag/compaction path as converged
    lanes. Killed lanes still flow through phase 2 (their finalize
    outputs are real, just early); the CALLER maps them to its
    error-score semantics using the controller's ``killed`` record.

    Phase 2 (finalize): run the finalize program over ALL tasks in
    original order through the ordinary round loop — outputs come back
    un-permuted, and the phase reuses the same chunk shape, so the
    whole call compiles at most three programs per (kernel, chunk).

    Dispatch depth is bounded at :data:`_MAX_ROUNDS_IN_FLIGHT` queued
    computations, same as the classic loop. Raises whatever the device
    raises on OOM (the caller downgrades to the classic path).

    ``live_rounds`` caps the rounds whose carries are on the device at
    once (None: all of them, the slice-major loop above). Where the
    carries outweigh the device — 50 lanes of 229 MB — the rounds past
    the cap wait their turn unstarted and enter as earlier ones retire,
    so a round runs its slices to its end before the next begins.

    Where ONE round is live, no rung controller is attached, the loop
    is pipelined and memory has room for a second slice's carry
    (``lanes_fit`` None, or at least ``2 * chunk``), the host reads the
    flags one slice behind: the round's next slice is enqueued on its
    device-resident carry before the flags of the slice in flight are
    read, so the flags' D2H, the retire test and the next dispatch go
    under device work; a round found done is read back once the next
    round's first slice is enqueued. Decisions still follow the carry
    whose flags were read, so outputs and counts are those of the
    in-step order; a round found done leaves one spare slice behind
    it, which runs no iteration. Elsewhere the flags are read in step.
    """
    depth = _MAX_ROUNDS_IN_FLIGHT if pipeline else 1
    put = plan.put
    shared = plan.shared
    shared_sig = plan._shared_sig
    n_devices = plan.n_task_slots * plan.data_shards

    def make_exec(fn, book=None):
        if not hasattr(fn, "lower"):
            # test doubles / non-AOT callables: run direct
            return lambda sl: fn(shared, sl)

        def run(sl):
            comp = compile_cache.aot_executable(
                fn, shared, sl, _leading_dim(sl), shared_sig=shared_sig
            )
            if book is not None and book not in stats:
                stats[book] = _program_bytes(comp)
                # a program of one device holds none: its text is
                # never read
                (stats["collective_ops_compiled"],
                 stats["collective_bytes_compiled"]) = (
                     _program_collectives(comp) if n_devices > 1
                     else (0, 0))
            return comp(shared, sl)

        return run

    init_exec = make_exec(plan.init_fn)
    # what the compiler says a round's step program holds, beside what
    # round sizing reckoned for it (``round_bytes_estimate``): the
    # device's own peak counter does not see a program's temporaries;
    # and the collectives the partitioner put into it
    step_exec = make_exec(plan.step_fn, "round_bytes_compiled")
    fin_exec = make_exec(plan.fin_fn)
    score_exec = (
        make_exec(plan.score_fn)
        if rung is not None and plan.score_fn is not None else None
    )

    with obs_trace.span("round_loop"):
        fin_carry = _compacted_slice_loop(
            spec, put, init_exec, step_exec, score_exec, task_args,
            n_tasks, chunk, stats,
            depth if live_rounds is None else min(depth, live_rounds),
            rung, live_rounds,
            look_ahead=(pipeline and rung is None
                        and (lanes_fit is None or 2 * chunk <= lanes_fit)),
        )
    # phase 2: finalize everything in ORIGINAL task order through the
    # ordinary round loop (same chunk shape -> same compiled program
    # for every finalize round, tail padded by _run_in_rounds)
    fin_stats = {}
    with obs_trace.span("finalize"):
        out = _run_in_rounds(
            lambda sh, sl: fin_exec(sl),
            {"task": task_args, "carry": fin_carry},
            shared, n_tasks, chunk, put=put, concat=True,
            pipeline=pipeline, stats=fin_stats, on_round=on_round,
        )
    stats["finalize"] = fin_stats
    return out


def _program_bytes(compiled):
    """Arguments, outputs and temporaries of a compiled program by its
    own ``memory_analysis()``, or None where the backend gives none."""
    try:
        ma = compiled.memory_analysis()
        return int(ma.temp_size_in_bytes + ma.argument_size_in_bytes
                   + ma.output_size_in_bytes)
    except Exception as exc:
        faults.log_suppressed("_program_bytes", exc, level=logging.DEBUG)
        return None


#: result bytes of an HLO element type
_HLO_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                 "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8,
                 "u64": 8, "f64": 8, "c64": 8, "c128": 16}
_HLO_COLLECTIVE = re.compile(
    r" = (.*?) (?:all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|all-to-all)(?:-done)?\(")
_HLO_SHAPE = re.compile(r"\b([a-z]+\d+|pred)\[([\d,]*)\]")


def hlo_collectives(hlo):
    """``[(result shapes, result bytes)]`` of every ``all-reduce``,
    ``all-gather``, ``reduce-scatter``, ``collective-permute`` and
    ``all-to-all`` instruction of an HLO text, each once as written
    (not times a loop's trips); of an asynchronous pair the ``-done``
    half, whose result is the collective's. A shape is its tuple of
    dimensions; the bytes are logical (no tile padding)."""
    found = []
    for match in _HLO_COLLECTIVE.finditer(hlo):
        shapes = [
            (dtype, tuple(int(n) for n in dims.split(",") if n))
            for dtype, dims in _HLO_SHAPE.findall(match.group(1))]
        found.append((
            [dims for _, dims in shapes],
            sum(_HLO_ITEMSIZE.get(dtype, 4) * math.prod(dims)
                for dtype, dims in shapes)))
    return found


def _program_collectives(compiled):
    """``(count, summed result bytes)`` of the collectives in a
    compiled program of several devices (:func:`hlo_collectives`);
    ``(None, None)`` where the program gives no text."""
    try:
        ops = hlo_collectives(compiled.as_text())
    except Exception as exc:
        faults.log_suppressed("_program_collectives", exc,
                              level=logging.DEBUG)
        return None, None
    return len(ops), int(sum(size for _, size in ops))


def _compacted_slice_loop(spec, put, init_exec, step_exec, score_exec,
                          task_args, n_tasks, chunk, stats, depth, rung,
                          live_rounds=None, look_ahead=False):
    """Phase 1 of :func:`_run_compacted` (its ``round_loop`` span): the
    slices, the flags gathers, the rungs and the compactions. Books the
    loop's accounting into ``stats`` and returns the per-task store of
    the ``finalize_keys`` carry leaves, in task order. ``look_ahead``
    lets a lone live round keep one slice enqueued beyond the one whose
    flags the host reads (``slices_ahead`` counts those dispatches,
    ``spare_slices`` the ones that found their round already done)."""
    import jax

    rounds = []
    for start in range(0, n_tasks, chunk):
        stop = min(start + chunk, n_tasks)
        sl = jax.tree_util.tree_map(lambda a: a[start:stop], task_args)
        rounds.append(_LiveRound(
            np.arange(start, stop), _pad_tail(sl, chunk - (stop - start))
        ))

    # rounds past the residency cap wait, unstarted and off the device
    waiting = []
    if live_rounds and len(rounds) > live_rounds:
        rounds, waiting = rounds[:live_rounds], rounds[live_rounds:]

    stats.update({
        "mode": "compacted", "chunk": int(chunk), "slices": 0,
        "compactions": 0, "rounds_per_slice": [], "retired_per_slice": [],
        "dispatch_s": 0.0, "flags_wait_s": 0.0,
        # retirement-reason split (satellite observability): totals by
        # cause plus the per-rung kill histogram the smoke asserts
        "retired_rung": 0, "retired_convergence": 0, "rung_history": [],
        "rung_wait_s": 0.0, "slices_ahead": 0, "spare_slices": 0,
    })
    counting = bool(spec.count_keys)
    if counting:
        # lane-slot occupancy: slots dispatched against slots that
        # carried a real, still-running fit when their round was
        # enqueued (the rest is padding and lanes riding on after done)
        stats.update({"lane_slots": 0, "live_lane_slots": 0})

    # per-task store of the carry leaves that leave the device as lanes
    # retire (the finalize subset, plus the spec's work counters);
    # allocated lazily from the first retired leaf
    retire_keys = spec.finalize_keys + tuple(
        k for k in spec.count_keys if k not in spec.finalize_keys
    )
    fin_store = {}

    # rung kills are a HOST-side verdict: the device carry's done leaf
    # knows nothing about them, so every fresh flags gather would
    # resurrect a killed lane. The kill mask persists across slices and
    # is OR-ed into each round's host flags right after every gather.
    killed_mask = np.zeros(n_tasks, dtype=bool) if rung is not None else None

    def apply_kills():
        for r in rounds:
            keep = len(r.idx)
            m = killed_mask[r.idx]
            if m.any():
                done = np.asarray(r.done).astype(bool)
                done[:keep][m] = True
                r.done = done

    def retire(idx_arr, subset):
        for key in retire_keys:
            leaf = np.asarray(subset[key])
            arr = fin_store.get(key)
            if arr is None:
                arr = np.zeros((n_tasks,) + leaf.shape[1:], leaf.dtype)
                fin_store[key] = arr
            arr[idx_arr] = leaf

    # lone rounds found done whose leaves are read back once the next
    # slice is enqueued: (task ids, kept lanes, the device leaves)
    retiring = []

    def read_retired():
        while retiring:
            idx_arr, keep, leaves = retiring.pop(0)
            retire(idx_arr, {k: _flags_only_gather(v)[:keep]
                             for k, v in leaves.items()})

    def live_lanes(r):
        keep = len(r.idx)
        return keep - (0 if r.done is None
                       else int(np.count_nonzero(r.done[:keep])))

    def enqueue(r, ahead=False):
        """One slice of ``r`` on its newest carry. ``ahead``: on a
        ``dev_carry`` whose flags are not read yet (the slice goes to
        ``r.ahead``)."""
        t_d = time.perf_counter()
        # a slice enqueued on a carry whose flags are unread books its
        # live lanes when those flags are read (flags_pop)
        unread = ahead or r.ahead is not None
        if counting:
            stats["lane_slots"] += int(chunk)
            if not unread:
                stats["live_lane_slots"] += live_lanes(r)
        with obs_trace.span("round_dispatch"):
            if r.dev_task is None:
                # task args never change between slices: place once
                # per round and reuse (keep masks at OvR scale are
                # chunk x n_samples — re-uploading them every slice
                # would undo the flags-only-D2H economy on the H2D
                # side)
                r.dev_task = put(r.task_sl)
            newest = r.ahead if r.ahead is not None else r.dev_carry
            if newest is None and r.host_carry is None:
                dev = init_exec(r.dev_task)
            else:
                carry_in = (
                    newest if newest is not None else put(r.host_carry)
                )
                r.host_carry = None
                dev = step_exec({"task": r.dev_task, "carry": carry_in})
        if r.ahead is not None:
            # the look-ahead's flags are the next to read
            r.dev_carry, r.ahead = r.ahead, dev
        elif ahead:
            r.ahead = dev
        else:
            r.dev_carry = dev
        try:
            leaf = dev[spec.done_key]
            if getattr(leaf, "is_fully_addressable", True):
                leaf.copy_to_host_async()
        except Exception as exc:
            # best-effort prefetch only; a real failure re-raises
            # at the blocking flags gather where it is classified
            faults.log_suppressed("_run_compacted.flags_prefetch",
                                  exc, level=logging.DEBUG)
        stats["rounds_per_slice"][-1] += 1
        stats["slices_ahead"] += int(unread)
        stats["dispatch_s"] += time.perf_counter() - t_d

    n_done_prev = 0
    while rounds:
        stats["slices"] += 1
        stats["rounds_per_slice"].append(0)
        pending = []
        # a lone round has no other round's program to hide its host
        # turn under: it hides it under its own next slice
        lone = look_ahead and len(rounds) == 1

        def flags_pop():
            r = pending.pop(0)
            # a slice is enqueued by now: the read-back goes under it
            read_retired()
            t_g = time.perf_counter()
            with obs_trace.span("flags_wait"):
                r.done = _flags_only_gather(r.dev_carry[spec.done_key])
            stats["flags_wait_s"] += time.perf_counter() - t_g
            if counting and r.ahead is not None:
                stats["live_lane_slots"] += live_lanes(r)

        for r in rounds:
            enqueue(r)
            if lone and r.ahead is None:
                enqueue(r, ahead=True)
            pending.append(r)
            while len(pending) >= depth:
                flags_pop()
        while pending:
            flags_pop()
        if killed_mask is not None and killed_mask.any():
            apply_kills()

        if score_exec is not None and rung.due(stats["slices"]):
            # ASHA rung: score every live lane's carry on device (the
            # score program reads the same device-resident task/carry
            # buffers the step program produced — no H2D at all) and
            # gather one (chunk,) f32 vector per round next to the
            # flags. The controller's losers are marked done HERE, on
            # the host copy of the flags, so the retire/compaction
            # logic below treats a rung kill exactly like convergence.
            t_r = time.perf_counter()
            with obs_trace.span("rung_eval"):
                scored = [
                    (r, score_exec({"task": r.dev_task,
                                    "carry": r.dev_carry}))
                    # an all-done round has no lane a rung could judge:
                    # scoring it would be a full discarded execution
                    for r in rounds
                    if not r.done[:len(r.idx)].astype(bool).all()
                ]
                for _r, dev_s in scored:
                    _start_host_copy(dev_s)
                live_ids = [np.empty(0, dtype=np.int64)]
                live_scores = [np.empty(0)]
                for r, dev_s in scored:
                    s = _flags_only_gather(dev_s)
                    keep = len(r.idx)
                    alive = ~r.done[:keep].astype(bool)
                    live_ids.append(r.idx[alive])
                    live_scores.append(np.asarray(s)[:keep][alive])
                killed = rung.decide(
                    np.concatenate(live_ids),
                    np.concatenate(live_scores),
                    stats["slices"],
                )
                if killed.size:
                    killed_mask[np.asarray(killed)] = True
                    apply_kills()
                    obs_trace.instant(
                        "rung_kill",
                        {"slice": int(stats["slices"]),
                         "n": int(killed.size)}
                        if obs_trace.enabled() else None,
                    )
            stats["rung_wait_s"] += time.perf_counter() - t_r

        # retire rounds whose real lanes are all done (the padding
        # lanes mirror a real lane and are ignored throughout)
        still = []
        n_alive = 0
        for r in rounds:
            keep = len(r.idx)
            done_lanes = r.done[:keep].astype(bool)
            n_alive += int((~done_lanes).sum())
            if done_lanes.all():
                if lone:
                    # the leaves cross while the next round's first
                    # slice runs, not before it is enqueued
                    leaves = {k: r.dev_carry[k] for k in retire_keys}
                    _start_host_copy(leaves)
                    retiring.append((r.idx, keep, leaves))
                else:
                    retire(r.idx, {
                        k: _flags_only_gather(r.dev_carry[k])[:keep]
                        for k in retire_keys
                    })
                r.dev_carry = None
                if r.ahead is not None:
                    # enqueued on a carry now known done: it ran no
                    # iteration, and nothing reads it
                    stats["spare_slices"] += 1
                    r.ahead = None
            else:
                still.append(r)
        # newly-finished lanes this slice (lanes already compacted out
        # of the rounds were counted when they finished; the lanes of
        # waiting rounds are alive)
        n_waiting = sum(len(r.idx) for r in waiting)
        newly_retired = (n_tasks - n_alive - n_waiting) - n_done_prev
        stats["retired_per_slice"].append(newly_retired)
        if newly_retired and obs_trace.enabled():
            obs_trace.instant(
                "lane_retire",
                {"slice": int(stats["slices"]), "n": int(newly_retired)},
            )
        n_done_prev = n_tasks - n_alive - n_waiting
        if not still and not waiting:
            break
        needed = -(-n_alive // chunk)
        if needed < len(still):
            # compaction event: the survivors fit in fewer rounds. This
            # is the one place surviving carries cross the host — full
            # gather for live lanes, finalize-subset only for the lanes
            # retiring out of mixed rounds.
            stats["compactions"] += 1
            id_parts, carry_parts = [], []
            for r in still:
                keep = len(r.idx)
                alive = ~r.done[:keep].astype(bool)
                host_c = _gather_host(r.dev_carry)
                r.dev_carry = None
                if not alive.all():
                    retire(r.idx[~alive], {
                        k: np.asarray(host_c[k])[:keep][~alive]
                        for k in retire_keys
                    })
                id_parts.append(r.idx[alive])
                carry_parts.append(jax.tree_util.tree_map(
                    lambda a: np.asarray(a)[:keep][alive], host_c
                ))
            alive_ids = np.concatenate(id_parts)
            packed = jax.tree_util.tree_map(
                lambda *xs: np.concatenate(xs), *carry_parts
            )
            rounds = []
            for i in range(needed):
                lo, hi = i * chunk, min((i + 1) * chunk, n_alive)
                ids = alive_ids[lo:hi]
                pad = chunk - (hi - lo)
                r = _LiveRound(ids, _pad_tail(
                    jax.tree_util.tree_map(
                        lambda a: np.asarray(a)[ids], task_args
                    ), pad,
                ))
                r.host_carry = _pad_tail(
                    jax.tree_util.tree_map(lambda a: a[lo:hi], packed), pad
                )
                rounds.append(r)
        else:
            rounds = still
        while waiting and len(rounds) < live_rounds:
            rounds.append(waiting.pop(0))

    read_retired()
    # the converged schema's "rounds": the slice loop's actual device
    # dispatches (one per live round per slice, spare slices included;
    # the finalize phase's rounds are tallied separately under
    # stats["finalize"])
    stats["rounds"] = int(sum(stats["rounds_per_slice"]))
    # retirement-reason accounting: every lane either converged (or hit
    # its iteration cap) or was killed by a rung — the quality/
    # convergence split the iterative stats dict exposes
    if rung is not None:
        stats["retired_rung"] = len(rung.killed)
        stats["rung_history"] = [dict(h) for h in rung.history]
    stats["retired_convergence"] = n_tasks - stats["retired_rung"]
    # per-task work counts, in the task axis's order
    for name, key in zip(("iters", "fevals"), spec.count_keys):
        stats[name] = fin_store[key].tolist()
    return {k: fin_store[k] for k in spec.finalize_keys}


#: width the init program is traced at to read a lane's footprint: a
#: prime no data dimension is likely to share, so a traced value is a
#: lane's own exactly when one of its dimensions is a multiple of it
_PROBE_LANES = 8191

#: ``_lane_footprint`` results per (init entry, shared shapes, task
#: shapes, slots): a fit after the first pays a dict lookup
_LANE_FOOTPRINTS = {}


def _lane_footprint(plan, task_args):
    """``(resident, transient, fixed, rows)`` bytes of the compacted
    path's programs, read from the dispatch's own trees before anything
    is compiled: what ONE lane keeps on its device between slices (its
    task slice and its carry), what it adds while its round runs — the
    most the traced program holds at once of values computed from the
    lane's data: the carry a step writes beside the one it reads, and
    whatever its fullest point keeps beside that (a line search along a
    ray holds both products' logits across its loop, a trial value and
    the residual beside them) — the largest value the program derives
    from the shared operands alone (a copy in another layout, say),
    which it holds once whatever the round's width, and the most it
    holds at once of values shaped like the data's rows (logits and
    their kin: what makes a lane heavy where the weights are small).

    On a mesh with a ``data`` axis (``plan.data_shards`` devices share
    the data's rows) every count is of what ONE device holds: a value
    with an axis of the data's rows counts a shard of it, weights and
    history the whole.

    All but the task slice come from one abstract trace of the init
    program (it runs the same solver slice the step program does) at
    :data:`_PROBE_LANES` lanes a slot; no data moves and nothing is
    lowered. The count is of the program AS TRACED: a value XLA fuses
    away is counted, the tiles it pads a small axis to are not. An
    entry that cannot be traced (a test double) or whose trace fails
    leaves the task slice alone."""
    import jax

    task_sig = tuple((tuple(l.shape[1:]), str(l.dtype))
                     for l in jax.tree_util.tree_leaves(task_args))
    shards = plan.data_shards
    key = (plan.init_fn, plan._shared_sig, task_sig, plan.n_task_slots,
           shards)
    found = _LANE_FOOTPRINTS.get(key)
    if found is None:
        task_bytes = tree_nbytes(task_args) // max(
            1, _leading_dim(task_args))
        found = (task_bytes, 0, 0, 0)
        if hasattr(plan.init_fn, "trace"):
            # the miss alone: seconds of Python tracing, once a shape
            with obs_trace.span("lane_footprint"):
                try:
                    carry, live, shared_top, rows = _traced_lane_bytes(
                        plan.init_fn, plan.shared, task_args,
                        _PROBE_LANES * plan.n_task_slots, shards,
                    )
                    found = (task_bytes + carry, live, shared_top, rows)
                except Exception as exc:
                    faults.log_suppressed("_lane_footprint", exc)
        _LANE_FOOTPRINTS[key] = found
    return found


def _sample_rows(shared):
    """The data's row count: the leading dimension most leaves of the
    shared operands have (X, y, the sample weights), or None."""
    import jax

    dims = collections.Counter(
        int(l.shape[0]) for l in jax.tree_util.tree_leaves(shared)
        if getattr(l, "ndim", 0) and l.shape[0] > 1)
    return dims.most_common(1)[0][0] if dims else None


def _traced_lane_bytes(init_fn, shared, task_args, width, shards=1):
    """One abstract trace of ``init_fn`` at ``width`` lanes (a value
    with an axis of the data's row count at a ``shards``-th of its
    bytes: what one of the devices that share the rows holds): the bytes
    of one lane's carry (the program's output); the most bytes of
    values with a lane axis (a dimension that is a multiple of
    ``width``) that the program computes and holds AT ONCE, per lane —
    a walk over the jaxpr in order, a value alive from the equation
    that makes it to the last that reads it, a loop's or a call's body
    counted at its own fullest point on top of what is alive around
    it; the bytes of the largest value without a lane axis; and the
    same walk over the lane values that have an axis of the data's row
    count."""
    import jax

    rows = _sample_rows(shared)

    def nbytes(aval):
        whole = math.prod(aval.shape) * getattr(aval.dtype, "itemsize", 4)
        return whole // shards if rows in aval.shape else whole

    def lane_bytes(aval):
        if any(n and n % width == 0 for n in aval.shape):
            return nbytes(aval) // width
        return 0

    def row_bytes(aval):
        return lane_bytes(aval) if rows in aval.shape else 0

    shared_top = 0

    def peak(jaxpr, size):
        """The most ``size`` bytes of the values this jaxpr computes
        that are alive at once (its own inputs are its caller's)."""
        nonlocal shared_top
        last = {var: i for i, eqn in enumerate(jaxpr.eqns)
                for var in eqn.invars if hasattr(var, "count")}
        last.update((var, len(jaxpr.eqns)) for var in jaxpr.outvars
                    if hasattr(var, "count"))
        live = top = 0
        dying = collections.Counter()
        for i, eqn in enumerate(jaxpr.eqns):
            made = 0
            for var in eqn.outvars:
                if not hasattr(var.aval, "shape"):
                    continue
                made += size(var.aval)
                dying[last.get(var, i)] += size(var.aval)
                # (a transposed operand is the contraction's own
                # reading of it, not a value the program keeps)
                if (not lane_bytes(var.aval)
                        and eqn.primitive.name != "transpose"):
                    shared_top = max(shared_top, nbytes(var.aval))
            # the bodies of loops, conditionals and calls
            inner = 0
            for param in eqn.params.values():
                for sub in (param if isinstance(param, (tuple, list))
                            else (param,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        inner = max(inner, peak(sub, size))
            top = max(top, live + max(made, inner))
            live += made - dying.pop(i, 0)
        return top

    traced = init_fn.trace(shared, jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            (width,) + tuple(a.shape[1:]), a.dtype),
        task_args,
    ))
    carry = sum(
        nbytes(o) for o in jax.tree_util.tree_leaves(traced.out_info)
    ) // width
    jaxpr = traced.jaxpr.jaxpr
    return carry, peak(jaxpr, lane_bytes), shared_top, peak(jaxpr, row_bytes)


def _size_iterative_round(backend, plan, task_args, n_tasks, round_size,
                          headroom=0.85):
    """``(chunk, chunk_basis, lanes_fit)`` of one compacted dispatch:
    an explicit ``round_size`` slot-aligned as it always was, else
    :func:`iterative_chunk_size` on the bytes ``plan`` placed
    (``backend.last_shared_bytes``) and :func:`_lane_footprint`.
    ``lanes_fit`` is the memory cap in lanes a round, read BEFORE the
    first compile (a refused compile costs ~40 s and the compacted
    path's OOM route ends in the classic fallback): what is free on the
    device, at the headroom :func:`_aot_exec_fn` uses, less the
    program's own copy of shared data and every lane's resident bytes —
    all rounds' task slices and carries stay on the device between
    slices, whatever the round size — over what the lanes of the
    rounds in flight add. None where the device reports no memory
    stats (CPU): the shapes alone decide there. Every count is of ONE
    device: what is free on the mesh's first, the shared bytes and the
    lane's footprint as one device holds them (on a mesh with a
    ``data`` axis a row shard of X and of a lane's logits), the lanes
    of one slot of the task axis.

    Where that cap binds (basis ``memory``) the carries of the rounds
    that wait are what fills the device, so they stay off it: the loop
    runs ONE round at a time, to its end (:func:`_compacted_slice_loop`'s
    ``live_rounds``), and the same rule is asked again with the cap of
    that loop — what ONE running round may hold, ``lanes_fit`` as
    booked. On the v5e the 130,107-column text search (50 lanes of
    229 MB) so runs eight rounds of 7, the ``target_rounds`` answer,
    under a cap of 20. Whether fewer, wider rounds would be faster
    there is not settled (five rounds of 10 and eight of 7 were never
    read on one program and one data set: PERF.md, PR 28); nothing a
    round does there is shared by its lanes, so the rule has no reason
    to widen it. A running lane held 0.85 GB where ``resident +
    transient`` reads 0.67 GB, so a cap that binds at its edge is
    untried."""
    d = plan.n_task_slots
    if round_size:
        chunk = int(math.ceil(min(n_tasks, round_size) / d) * d)
        return chunk, "round_size", None
    resident, transient, fixed = _lane_footprint(plan, task_args)[:3]
    shared, lane = backend.last_shared_bytes or 0, resident + transient
    free = backend._free_device_bytes()
    if free is None or free <= 0:
        return _iterative_chunk(n_tasks, d, shared, lane, None) + (None,)
    room = int(free * headroom) - fixed
    lanes_fit = d * max(1, (room - -(-n_tasks // d) * resident)
                        // max(1, _MAX_ROUNDS_IN_FLIGHT * transient))
    chunk, basis = _iterative_chunk(n_tasks, d, shared, lane, lanes_fit)
    if basis == "memory":
        lanes_fit = d * max(1, room // max(1, lane))
        chunk, _ = _iterative_chunk(n_tasks, d, shared, lane, lanes_fit)
    return chunk, basis, lanes_fit


#: AOT executables live in compile_cache (keyed by (jit fn, shared
#: shape sig, chunk) — the jit fn itself is memoised structurally, so
#: this composes to the same lifetime jit's own cache would have had,
#: plus hit/miss counters and the on-disk write-through)
_shape_sig = compile_cache.shape_sig


def _aot_exec_fn(fn, shared_args, task_args, chunk, d, free_bytes,
                 headroom=0.85):
    """Return ``(exec_fn, chunk)`` for the round loop.

    ``exec_fn(shared, task_slice)`` runs an AOT-compiled executable for
    the slice's chunk size (compiled lazily per chunk, cached across
    fits). When ``free_bytes`` is known, the requested chunk's program
    is compiled up front and its ``memory_analysis()`` footprint
    (temps + outputs + task arguments; shared arguments are already
    device-resident and excluded from ``free_bytes``) is scaled
    linearly per task to shrink the first round to ``headroom`` of free
    memory — one extra compile at most when the requested chunk
    compiles, and none when it already fits. A compile that fails for
    anything but memory raises here, where it happened.
    """
    import jax

    if not hasattr(fn, "lower"):
        # not an AOT-capable jit function (e.g. a test double): run it
        # directly and rely on the reactive backstop alone
        return fn, chunk

    shared_sig = _shape_sig(shared_args)

    def _compiled_for(n_chunk, task_like):
        return compile_cache.aot_executable(
            fn, shared_args, task_like, n_chunk, shared_sig=shared_sig
        )

    def exec_fn(shared, sl):
        n_chunk = _leading_dim(sl)
        return _compiled_for(n_chunk, sl)(shared, sl)

    if free_bytes is None or free_bytes <= 0:
        return exec_fn, chunk

    # A round too big for the device fails AT COMPILE TIME on a TPU
    # (RESOURCE_EXHAUSTED from the compiler's own allocation plan), so
    # the footprint is read from the largest round that compiles: the
    # requested one, else an eighth of it (then an eighth of that) — a
    # refused compile costs as much as a good one, so the probe steps
    # down fast and the linear estimate picks the size from there.
    sized = chunk
    while True:
        try:
            compiled = _compiled_for(sized, task_args)
            break
        except Exception as exc:
            if faults.classify(exc) != faults.OOM or sized <= d:
                raise
            faults.record("rounds_refused")
            sized = max(d, (sized // 8) // d * d)
    try:
        ma = compiled.memory_analysis()
        task_arg_bytes = sum(
            int(np.prod(l.shape[1:])) * l.dtype.itemsize * sized
            for l in jax.tree_util.tree_leaves(task_args)
        )
        # temps are live for the one round executing; args + outputs
        # are resident for every in-flight round (dispatch depth is
        # bounded at _MAX_ROUNDS_IN_FLIGHT in _run_in_rounds)
        needed = (
            int(ma.temp_size_in_bytes)
            + _MAX_ROUNDS_IN_FLIGHT
            * (int(ma.output_size_in_bytes) + task_arg_bytes)
        )
    except Exception as exc:
        # no analysis on this backend: reactive backstop only
        faults.log_suppressed("_aot_exec_fn.memory_analysis", exc,
                              level=logging.DEBUG)
        return exec_fn, sized

    allowed = int(free_bytes * headroom)
    if sized < chunk or (needed > allowed and chunk > d):
        per_task = max(1, needed // sized)
        new_chunk = min(chunk, max(d, (allowed // per_task) // d * d))
        if sized < chunk:
            # the requested round was refused whatever the estimate
            # says: never come back with more than half of it
            new_chunk = min(new_chunk, max(d, (chunk // 2) // d * d))
        if new_chunk < chunk:
            warnings.warn(
                (f"batched_map: compiled round footprint ~{needed >> 20} "
                 f"MiB exceeds {allowed >> 20} MiB free" if sized == chunk
                 else
                 f"batched_map: a round of {chunk} tasks does not compile "
                 f"into device memory (~{per_task >> 20} MiB a task, read "
                 f"from a round of {sized}; {allowed >> 20} MiB free)")
                + f"; starting at round_size={new_chunk} (pass partitions "
                "to override)"
            )
            chunk = new_chunk
    return exec_fn, chunk


#: jit(vmap(kernel)) memo lives in compile_cache; this module-level
#: alias is the seam tests monkeypatch (batched_map resolves the name
#: dynamically) and callers pass positional (kernel, static_args,
#: task_sharding, shared_shardings, cache_key, donate_tasks)
def _jit_vmapped(kernel, static_args, task_sharding=None,
                 shared_shardings=None, cache_key=None, donate_tasks=False):
    return compile_cache.jit_vmapped(
        kernel, static_args, task_sharding, shared_shardings,
        cache_key=cache_key, donate_tasks=donate_tasks,
    )


def row_sharded_specs(backend, shared, sample_axes):
    """Build ``shared_specs`` for :meth:`TaskBackend.batched_map`.

    ``sample_axes`` maps shared-dict keys to the axis index holding the
    per-sample dimension (which rides the mesh 'data' axis); keys not
    listed replicate. Each batched-path call site declares its own
    layout explicitly. Returns None on 1D meshes (fully replicated).
    """
    if getattr(backend, "data_axis_size", 1) <= 1:
        return None
    from jax.sharding import PartitionSpec as P

    specs = {}
    for key in shared:
        ax = sample_axes.get(key)
        specs[key] = (
            None if ax is None else P(*([None] * ax), "data")
        )
    return specs


def resolve_backend(backend, n_jobs=None):
    """Normalise the user-facing ``backend=`` argument.

    Accepted: ``None`` (local serial/threads — the ``sc=None`` analogue),
    a TaskBackend instance, the strings ``'local'`` / ``'tpu'`` /
    ``'devices'``, or a ``jax.sharding.Mesh`` / explicit device list.
    """
    if backend is None or backend == "local":
        return LocalBackend(n_jobs=n_jobs)
    if isinstance(backend, TaskBackend):
        return backend
    if backend in ("tpu", "devices", "jax"):
        return TPUBackend(n_jobs=n_jobs)
    try:
        from jax.sharding import Mesh

        if isinstance(backend, Mesh):
            # the mesh is adopted whole — a 'data' axis keeps row-sharding
            return TPUBackend(mesh=backend, n_jobs=n_jobs)
    except ImportError:  # pragma: no cover
        pass
    if isinstance(backend, (list, tuple)):
        return TPUBackend(devices=backend, n_jobs=n_jobs)
    raise ValueError(f"Unrecognised backend: {backend!r}")
