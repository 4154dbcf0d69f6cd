"""
Streamed (out-of-core) fit drivers: the solver carry forms rewired to
consume a :class:`~skdist_tpu.data.ChunkedDataset` block by block
through the backend's double-buffered host→device pipeline
(``parallel.backend.BlockFeeder``).

Three family forms, selected by the estimator's ``_stream_fit_kind``:

- **"lbfgs"** (LogisticRegression, LinearSVC): the objective's data
  term is row-additive, so one evaluation of ``(f, g)`` at the current
  iterate is a streamed reduction — each block contributes
  ``value_and_grad`` of its block-local data loss (through the same
  ``LinearOperator`` matvec interface as the resident problem, dense or
  packed-CSR; on a mesh with a 'data' axis the block row-shards and
  GSPMD psums the partials), the regulariser is evaluated once, and the
  L-BFGS state machine (two-loop recursion, Armijo backtracking —
  mirroring ``models/solvers._lbfgs_body`` lane for lane: its ring of
  slots and the device's history in age order hold the same pairs in
  the same order) runs host-side over the task batch. Each line-search
  probe is a value-only streamed pass. Block accumulation reorders f32
  sums, so results agree with the resident solve to tolerance, not
  bitwise.
- **"sgd"** (SGDClassifier): epochs become block streams. An epoch
  visits blocks in order; within a block, mini-batches advance the
  ``(w, pstate, step, acc)`` carry through the SAME traced update as
  the resident scan (``solvers.sgd_batch_scan``), with the global epoch
  clock keying block-local shuffles. With ``shuffle=False`` and batch
  boundaries aligned to block boundaries, the visit order equals the
  resident scan's and the streamed fit is BITWISE identical to it.
  Early stopping applies sklearn's no-improvement rule at epoch
  boundaries exactly as the resident epoch body does.
- **"gram"** (Ridge family): the normal equations accumulate — each
  block contributes its ``(XᵀSX, XᵀST)`` partials, one small solve
  finishes per task.
- **"gbdt"** (DistHistGradientBoosting*): boosting rounds become
  binned-cache streams. Raw features are touched exactly twice up
  front (the quantile-sketch pass and the bin pass that writes the
  uint8 cache, both inside ``ChunkedDataset.with_binned_cache``);
  every boosting round then streams the ~4×-smaller cache: one
  histogram pass per tree level (per-node grad/hess histograms
  accumulated across blocks, psum'd over the mesh 'data' axis by
  GSPMD) plus one update pass advancing the margin carry ``F`` —
  which lives in host memmaps and rides the block tree, so device
  memory stays O(block). Split scoring runs the resident kernel's own
  ``histogram_node_scores``/``pick_level_splits`` on the gathered
  histograms, so resident-vs-streamed trees agree to f32 block-sum
  tolerance. The rung hook fires at every round boundary.

Every driver dispatches per-task batches (the CV search's candidate ×
fold axis, OvR's class axis) through one vmapped program whose task
axis shards over the backend mesh; fault handling is block-granular —
a transient fault re-dispatches the failed block with the reader
RE-OPENED at that offset (``BlockFeeder.seek``), a preemption restarts
the current pass after re-placing device state.
"""

import math
import os
import shutil
import tempfile
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..obs import metrics as obs_metrics
from ..parallel import faults
from ..parallel.backend import BlockFeeder, _RetryState, _RoundFault

__all__ = [
    "stream_fit_estimator",
    "stream_fit_tasks",
    "stream_scores",
    "lbfgs_stream",
]

_EPS = np.float32(1e-12)


# ---------------------------------------------------------------------------
# block plumbing
# ---------------------------------------------------------------------------

def _pad_rows_for(name):
    """Pad value for a per-row array appended to a padded block: all
    streamed row arrays pad with values that cannot influence a fit —
    weights pad 0 (excluded from every contraction), fold ids pad -1
    (never a real split id), labels pad 0 (a valid class index whose
    row has zero weight)."""
    return -1 if name == "fold" else 0


def _make_block_read(dataset, row_arrays, pad=True):
    """``read(i) -> host block tree`` composing the dataset's X block
    with driver-owned per-row vectors (encoded labels, weights, fold
    ids) sliced to the block's global row range."""

    def read(i):
        b = dataset.read_block(i, pad=pad)
        tree = {"X": b.X}
        s, e = b.start, b.stop
        rows = dataset.block_rows if pad else b.n_real
        pad_n = rows - b.n_real
        for name, arr in row_arrays.items():
            sl = np.asarray(arr[s:e])
            if pad_n:
                sl = np.concatenate([
                    sl,
                    np.full((pad_n,) + sl.shape[1:],
                            _pad_rows_for(name), sl.dtype),
                ])
            tree[name] = sl
        return tree

    return read


def _example_block(dataset, row_arrays, extra_scalars=()):
    """Zero-filled block tree with the runtime block's exact structure
    and shapes — what mesh backends with a 'data' axis need to resolve
    per-leaf block shardings without reading data."""
    from ..sparse import PackedX

    r = dataset.block_rows
    if dataset.x_format == "packed":
        X = PackedX(
            np.zeros((r, dataset.packed_m), np.int32),
            np.zeros((r, dataset.packed_m), np.float32),
            dataset.n_features,
        )
    else:
        X = np.zeros((r, dataset.n_features), np.float32)
    tree = {"X": X}
    for name, arr in row_arrays.items():
        arr = np.asarray(arr)
        tree[name] = np.zeros((r,) + arr.shape[1:], arr.dtype)
    for name in extra_scalars:
        tree[name] = np.int32(0)
    return tree


def _stream_stats(backend, sync):
    stats = backend.last_round_stats = obs_metrics.new_round_stats(
        "streamed",
        stream_mode="serial" if sync else "pipelined",
    )
    return stats


def _resolve_sync(backend, sync):
    return bool(getattr(backend, "sync_rounds", False)) if sync is None \
        else bool(sync)


class _BlockRetry:
    """Block-granular fault policy shared by every streamed pass: a
    retryable fault at block ``i`` seeks the feeder back to ``i`` (the
    reader re-opens at exactly that offset) and re-dispatches; budget
    accounting matches the round loop's per-round contract (the counter
    resets on progress). A PREEMPTED fault calls ``restart`` (the
    driver re-places device state and rewinds its accumulators) and
    seeks to the pass start."""

    def __init__(self, stats):
        self.retry = _RetryState()
        self.stats = stats

    def handle(self, exc, feeder, i, restart=None):
        kind = faults.classify(exc)
        if not faults.is_retryable(kind):
            raise exc
        self.retry.admit(_RoundFault([], 0, exc, kind), i)
        self.stats["retries"] = self.retry.total
        if kind == faults.PREEMPTED and restart is not None:
            restart()
            feeder.seek(0)
            return 0
        feeder.seek(i)
        return i


def _dispatch_seam():
    """The fault-injection seam: a planned transient/preempt/fatal
    fires here, where a real device dispatch would fail."""
    inj = faults.active_injector()
    if inj is not None:
        inj.round_dispatched()


def _elastic_replans(backend, plans):
    """The elastic half of a streamed PREEMPTED restart: let an
    elastic backend shrink its mesh to the surviving devices, then
    re-resolve every driver plan in place against the new mesh
    (:meth:`StreamPlan.rebuild`) BEFORE the caller re-places its task
    trees. The divisor rule of the mesh manager keeps the shrunken
    task extent dividing the full one, so task axes already padded to
    full-mesh slots re-place on the shrunken mesh unchanged — which is
    why a resumed streamed fit stays bitwise identical: the same
    lanes, the same block order, the same arithmetic, just fewer
    devices under them. No-op (False) on non-elastic backends."""
    if backend.elastic_preempted():
        for p in plans:
            p.rebuild()
        return True
    return False


def _n_tasks(task_args):
    return len(np.asarray(next(iter(task_args["hyper"].values()))))


def _take_tree(tree, idx):
    """Subset every task-axis leaf to the given lane indices — the
    task-batch SHRINK of a rung kill: retired lanes' slots compact
    away and later passes dispatch fewer programs."""
    return jax.tree_util.tree_map(lambda a: np.asarray(a)[idx], tree)


def _pad_tree_to(tree, T, Tp):
    """Pad every task-axis leaf to exactly ``Tp`` rows by repeating
    the last lane; padded lanes compute duplicate work and their
    outputs are sliced off."""
    if Tp == T:
        return tree
    pad = Tp - T
    return jax.tree_util.tree_map(
        lambda a: np.concatenate(
            [np.asarray(a), np.repeat(np.asarray(a)[-1:], pad, axis=0)]
        ),
        tree,
    )


def _slot_pad_tree(tree, T, slots):
    """Pad every task-axis leaf to a slot multiple by repeating the
    last lane — mesh task sharding needs a divisible axis (the
    streamed analogue of the round loop's tail padding)."""
    Tp = -(-T // max(1, int(slots))) * max(1, int(slots))
    return _pad_tree_to(tree, T, Tp), Tp


# ---------------------------------------------------------------------------
# streamed reductions (the L-BFGS / gram data passes)
# ---------------------------------------------------------------------------

def _streamed_sum(plan, read, n_blocks, tc, stats, sync, restart=None):
    """Sum ``plan.fn(block, tc)`` over all blocks (device-resident
    accumulator; one D2H at the end). ``tc`` may be a zero-arg callable
    re-evaluated per dispatch (so a preemption ``restart`` can swap in
    freshly-placed task trees). The reduction is block-order
    deterministic: serial and pipelined feeds produce bitwise-identical
    sums.

    Fault handling is two-tier, mirroring where XLA surfaces errors:
    dispatch-time faults retry at BLOCK granularity (the feeder
    re-opens the reader at the failed offset), while faults that only
    surface at the blocking gather (asynchronous dispatch poisons the
    whole accumulator chain) retry the PASS — same retry budget."""
    tc_fn = tc if callable(tc) else (lambda: tc)
    pass_guard = _BlockRetry(stats)
    while True:
        acc = None
        # late-bind placement through the plan object: an elastic
        # restart rebuilds the plan in place mid-pass, and the feeder
        # must place subsequent blocks on the NEW mesh
        feeder = BlockFeeder(read, n_blocks, lambda t: plan.put_block(t),
                             sync=sync, stats=stats)
        guard = _BlockRetry(stats)
        try:
            while True:
                item = feeder.next()
                if item is None:
                    break
                i, dev = item
                t0 = time.perf_counter()
                try:
                    _dispatch_seam()
                    out = plan.fn(dev, tc_fn())
                except Exception as exc:
                    preempted = faults.classify(exc) == faults.PREEMPTED
                    guard.handle(exc, feeder, i, restart=restart)
                    if preempted and restart is not None:
                        acc = None  # device accumulator presumed lost
                    continue
                acc = out if acc is None else jax.tree_util.tree_map(
                    jnp.add, acc, out
                )
                stats["dispatch_s"] += time.perf_counter() - t0
        finally:
            feeder.close()
        try:
            return jax.device_get(acc)
        except Exception as exc:
            # an async fault re-surfacing at the gather: the failed
            # block is unknowable, so the whole pass re-runs
            kind = faults.classify(exc)
            if not faults.is_retryable(kind):
                raise
            pass_guard.retry.admit(_RoundFault([], 0, exc, kind), 0)
            stats["retries"] = pass_guard.retry.total
            if kind == faults.PREEMPTED and restart is not None:
                restart()


# ---------------------------------------------------------------------------
# host-side batched L-BFGS (mirrors models/solvers._lbfgs_body)
# ---------------------------------------------------------------------------

def _two_loop_batch(g, S, Y, rho, k):
    T, m, P = S.shape
    rT = np.arange(T)
    n_corr = np.minimum(k, m)
    q = g.astype(np.float32).copy()
    alphas = np.zeros((T, m), np.float32)
    for i in range(m):
        idx = (k - 1 - i) % m
        valid = i < n_corr
        alpha = rho[rT, idx] * np.einsum("tp,tp->t", S[rT, idx], q)
        alpha = np.where(valid, alpha, np.float32(0.0)).astype(np.float32)
        q = q - alpha[:, None] * Y[rT, idx]
        alphas[rT, idx] = alpha
    last = (k - 1) % m
    sy = np.einsum("tp,tp->t", S[rT, last], Y[rT, last])
    yy = np.einsum("tp,tp->t", Y[rT, last], Y[rT, last])
    gamma = np.where(k > 0, sy / (yy + _EPS), np.float32(1.0))
    r = gamma.astype(np.float32)[:, None] * q
    for i in range(m):
        idx = (k - n_corr + i) % m
        valid = i < n_corr
        beta = rho[rT, idx] * np.einsum("tp,tp->t", Y[rT, idx], r)
        upd = S[rT, idx] * (alphas[rT, idx] - beta.astype(np.float32))[:, None]
        r = r + np.where(valid[:, None], upd, np.float32(0.0))
    return -r


def lbfgs_stream(eval_fg, eval_f, w0, tol, max_iter, history=10,
                 max_ls=20, pass_hook=None):
    """Batched L-BFGS whose objective evaluations are STREAMED passes.

    ``eval_fg(W (T,P) f32) -> (f (T,), g (T,P))`` and ``eval_f`` are
    full-objective evaluations (block-accumulated data term + the
    regulariser); the state machine here mirrors
    ``models/solvers._lbfgs_body`` lane for lane — same Armijo
    constants, direction-normalisation rule, curvature filter, and
    ``done`` semantics (converged at ``tol`` | line-search stall |
    iteration cap) — in host numpy f32 over the task batch, with frozen
    lanes masked out of every update. Returns ``(W, n_iter, done)``
    indexed by the ORIGINAL lane order.

    ``pass_hook(pass_idx, lane_ids, w, it, done) -> killed lane ids``
    is the rung seam, called after every iteration (= one block-pass
    group of the dataset): ``lane_ids`` maps the batch's current rows
    to original lanes. Lanes the hook kills are recorded at their
    kill-time iterate and COMPACTED out of every solver array, so
    subsequent streamed evaluations dispatch a smaller task batch.
    Lanes are independent in the batched recursion (every reduction is
    per-lane, the lockstep line search halves per-lane step sizes), so
    survivor trajectories are bitwise identical under compaction.
    """
    w = np.ascontiguousarray(w0, dtype=np.float32)
    T, P = w.shape
    m = int(history)
    tol = np.asarray(tol, dtype=np.float32).reshape(T)
    lanes = np.arange(T)
    out_w = w.copy()
    out_it = np.zeros(T, np.int64)
    out_done = np.zeros(T, bool)
    f, g = eval_fg(w)
    f = np.asarray(f, np.float32).reshape(T)
    g = np.asarray(g, np.float32).reshape(T, P)
    S = np.zeros((T, m, P), np.float32)
    Y = np.zeros((T, m, P), np.float32)
    rho = np.zeros((T, m), np.float32)
    k = np.zeros(T, np.int64)
    it = np.zeros(T, np.int64)
    done = (np.max(np.abs(g), axis=1) <= tol) | (max_iter <= 0)
    rT = np.arange(T)
    pass_idx = 0
    while done.size and not done.all():
        # a pass_hook kill compacts every lane array — the iteration's
        # temporaries must track the LIVE batch size, not the original
        T = lanes.size
        d = _two_loop_batch(g, S, Y, rho, k)
        gd0 = np.einsum("tp,tp->t", g, d)
        descent = gd0 < 0
        d = np.where(descent[:, None], d, -g)
        raw_scale = (~descent) | (k == 0)
        norm = np.linalg.norm(d, axis=1).astype(np.float32) + _EPS
        d = np.where(raw_scale[:, None], d / norm[:, None], d)
        gd = np.einsum("tp,tp->t", g, d).astype(np.float32)
        # Armijo backtracking, lockstep over lanes (each full-objective
        # probe is one streamed pass over every block)
        t_step = np.ones(T, np.float32)
        f_new = np.asarray(
            eval_f((w + t_step[:, None] * d).astype(np.float32)),
            np.float32,
        ).reshape(T)
        ls_it = np.zeros(T, np.int64)
        armijo = f_new <= f + np.float32(1e-4) * t_step * gd
        active = (~armijo) & (ls_it < max_ls) & (~done)
        while active.any():
            t_step = np.where(active, t_step * np.float32(0.5), t_step)
            f_try = np.asarray(
                eval_f((w + t_step[:, None] * d).astype(np.float32)),
                np.float32,
            ).reshape(T)
            f_new = np.where(active, f_try, f_new)
            ls_it = ls_it + active
            armijo = f_new <= f + np.float32(1e-4) * t_step * gd
            active = (~armijo) & (ls_it < max_ls) & (~done)
        ok = f_new <= f + np.float32(1e-4) * t_step * gd
        w_new = (w + t_step[:, None] * d).astype(np.float32)
        f2, g_new = eval_fg(w_new)
        f2 = np.asarray(f2, np.float32).reshape(T)
        g_new = np.asarray(g_new, np.float32).reshape(T, P)
        s = w_new - w
        yv = g_new - g
        sy = np.einsum("tp,tp->t", s, yv)
        store = (sy > 1e-10) & (~done)
        idx = k % m
        S[rT[store], idx[store]] = s[store]
        Y[rT[store], idx[store]] = yv[store]
        rho[rT[store], idx[store]] = (
            np.float32(1.0) / (sy[store].astype(np.float32) + _EPS)
        )
        live = ~done
        converged = np.max(np.abs(g_new), axis=1) <= tol
        stalled = ~ok
        w = np.where(live[:, None], w_new, w)
        f = np.where(live, f2, f)
        g = np.where(live[:, None], g_new, g)
        k = k + (store & live)
        it = it + live
        done = np.where(
            live, converged | stalled | (it >= max_iter), done
        )
        pass_idx += 1
        if pass_hook is not None:
            killed = np.asarray(
                pass_hook(pass_idx, lanes, w, it, done), dtype=np.int64
            ).reshape(-1)
            if killed.size:
                drop = np.isin(lanes, killed)
                out_w[lanes[drop]] = w[drop]
                out_it[lanes[drop]] = it[drop]
                out_done[lanes[drop]] = done[drop]
                keep = ~drop
                w, f, g = w[keep], f[keep], g[keep]
                S, Y, rho = S[keep], Y[keep], rho[keep]
                k, it, done, tol = k[keep], it[keep], done[keep], tol[keep]
                lanes = lanes[keep]
                rT = np.arange(lanes.size)
    out_w[lanes] = w
    out_it[lanes] = it
    out_done[lanes] = done
    return out_w, out_it, out_done


# ---------------------------------------------------------------------------
# family kernel builders
# ---------------------------------------------------------------------------

def _stream_key(est_cls, static, meta, part, extra=()):
    from .linear import _meta_signature
    from ..parallel import structural_key

    return structural_key(
        "stream", est_cls, part, static, _meta_signature(meta), *extra
    )


def _default_derive(block, task):
    """Single-fit / no-fold derive: labels and weights ride the block;
    fold-masked variants are composed by the CV/OvR call sites."""
    return block["X"], block["y"], block["sw"], task["hyper"]


def _lbfgs_stream_kernels(est_cls, meta, static, derive):
    """The three jit programs of one streamed L-BFGS family config:
    per-block data (f, g), per-block data f (line-search probes), and
    the one-shot regulariser (f, g) evaluated on a zero block."""
    from .linear import maybe_exact_matmuls

    problem = est_cls._build_fit_problem(meta, static)

    def fg_kernel(block, tc):
        Xb, yb, swb, hyper = derive(block, tc["task"])
        parts = problem(Xb, yb, swb, hyper, parts=True)
        f, g = jax.value_and_grad(parts[3])(tc["W"])
        return {"f": f, "g": g}

    def f_kernel(block, tc):
        Xb, yb, swb, hyper = derive(block, tc["task"])
        parts = problem(Xb, yb, swb, hyper, parts=True)
        return {"f": parts[3](tc["W"])}

    def reg_kernel(block, tc):
        Xb, yb, swb, hyper = derive(block, tc["task"])
        parts = problem(Xb, yb, swb, hyper, parts=True)
        f, g = jax.value_and_grad(parts[4])(tc["W"])
        return {"f": f, "g": g}

    wrap = lambda fn: maybe_exact_matmuls(est_cls, fn)
    return wrap(fg_kernel), wrap(f_kernel), wrap(reg_kernel)


def _host_unpack(est_cls, meta, static, dataset):
    """The family's ``unpack`` closure, recovered host-side from a
    one-row zero problem (unpack only reshapes; it never touches X)."""
    from ..sparse import PackedX

    problem = est_cls._build_fit_problem(meta, static)
    if dataset.x_format == "packed":
        Xz = PackedX(np.zeros((1, 1), np.int32), np.zeros((1, 1), np.float32),
                     meta["n_features"])
    else:
        Xz = np.zeros((1, meta["n_features"]), np.float32)
    hyper = {
        name: np.float32(1.0)
        for name in getattr(est_cls, "_hyper_names", ())
    }
    out = problem(Xz, np.zeros(1, np.int32), np.zeros(1, np.float32), hyper)
    return out[2]


# ---------------------------------------------------------------------------
# the drivers
# ---------------------------------------------------------------------------

def _check_data_axis_geometry(backend, dataset):
    """2D (task x data) meshes row-shard every placed block: the padded
    block height must split evenly over the 'data' axis, or GSPMD's
    device_put rejects the block with an opaque divisibility error —
    fail here with the remedy instead."""
    dsize = getattr(backend, "data_axis_size", 1)
    if dsize > 1 and dataset.block_rows % dsize:
        raise ValueError(
            f"block_rows={dataset.block_rows} does not divide over the "
            f"mesh 'data' axis (data_axis_size={dsize}); rebuild the "
            "ChunkedDataset with a block_rows that is a multiple of "
            "the data axis size"
        )


def _zero_block_dev(plan, dataset, row_arrays, extra_scalars=(), rows=1):
    """A zero block of ``rows`` rows (all weight-0 padding), placed
    once — the regulariser kernels' dummy shared tree. ``rows`` is the
    mesh's data-axis size on 2D backends: even a dummy block must be
    row-shardable onto the 'data' axis."""
    from ..sparse import PackedX

    rows = max(1, int(rows))
    if dataset.x_format == "packed":
        X = PackedX(np.zeros((rows, dataset.packed_m), np.int32),
                    np.zeros((rows, dataset.packed_m), np.float32),
                    dataset.n_features)
    else:
        X = np.zeros((rows, dataset.n_features), np.float32)
    tree = {"X": X}
    for name, arr in row_arrays.items():
        arr = np.asarray(arr)
        tree[name] = np.full(
            (rows,) + arr.shape[1:], _pad_rows_for(name), arr.dtype
        )
    for name in extra_scalars:
        tree[name] = np.int32(0)
    return plan.put_block(tree)


def _fit_lbfgs_stream(backend, est_cls, meta, static, dataset, row_arrays,
                      task_args, derive, stats, sync, key_extra=(),
                      w_init=None, rung_hook=None):
    st = dict(static)
    max_iter, history = int(st["max_iter"]), int(st["history"])
    width = est_cls._flat_w_width(meta, static)
    T = _n_tasks(task_args)
    fg_kernel, f_kernel, reg_kernel = _lbfgs_stream_kernels(
        est_cls, meta, static, derive
    )
    example = _example_block(dataset, row_arrays)
    plan_fg = backend.prepare_streamed(
        fg_kernel, example,
        cache_key=_stream_key(est_cls, static, meta, "lbfgs_fg", key_extra),
    )
    plan_f = backend.prepare_streamed(
        f_kernel, example,
        cache_key=_stream_key(est_cls, static, meta, "lbfgs_f", key_extra),
    )
    plan_reg = backend.prepare_streamed(
        reg_kernel, example,
        cache_key=_stream_key(est_cls, static, meta, "lbfgs_reg", key_extra),
    )
    read = _make_block_read(dataset, row_arrays, pad=True)
    n_blocks = dataset.n_blocks

    # the solver runs over the LIVE lane subset; a rung kill shrinks
    # sel["idx"] and re-places the task tree, so subsequent passes
    # stream the same bytes through fewer programs. Slot padding (mesh
    # task sharding needs a divisible axis) happens at the dispatch
    # seam on the live subset only.
    sel = {"idx": np.arange(T)}
    state = {}
    zero_dev = {}

    def place_tasks(fresh=True):
        # ``fresh`` recomputes the padded width from the current slot
        # count; an elastic restart keeps the previous width instead
        # (the largest-divisor re-layout guarantees it still divides)
        # so mid-pass device state stays size-consistent.
        L = sel["idx"].size
        if fresh or "Lp" not in state:
            slots = max(1, int(plan_fg.n_task_slots))
            state["Lp"] = -(-L // slots) * slots
        state["tasks"] = plan_fg.put_task(
            _pad_tree_to(_take_tree(task_args, sel["idx"]), L, state["Lp"])
        )
        zero_dev["b"] = _zero_block_dev(
            plan_reg, dataset, row_arrays,
            rows=getattr(backend, "data_axis_size", 1),
        )

    place_tasks()

    def restart():
        # preemption: device state presumed lost — shrink an elastic
        # mesh to the survivors (rebuilding the three plans), then
        # re-place the task tree and the regulariser's zero block
        _elastic_replans(backend, (plan_fg, plan_f, plan_reg))
        place_tasks(fresh=False)
        faults.record("shared_replacements")

    def _pad_W(W):
        L, Lp = W.shape[0], state["Lp"]
        if Lp == L:
            return W
        return np.concatenate([W, np.repeat(W[-1:], Lp - L, axis=0)])

    def eval_fg(W):
        W = np.ascontiguousarray(W, np.float32)
        L = W.shape[0]
        tc = lambda: {"task": state["tasks"],
                      "W": plan_fg.put_task(_pad_W(W))}
        acc = _streamed_sum(plan_fg, read, n_blocks, tc, stats, sync,
                            restart=restart)
        reg = jax.device_get(plan_reg.fn(zero_dev["b"], tc()))
        return (np.asarray(acc["f"])[:L] + np.asarray(reg["f"])[:L],
                np.asarray(acc["g"])[:L] + np.asarray(reg["g"])[:L])

    def eval_f(W):
        W = np.ascontiguousarray(W, np.float32)
        L = W.shape[0]
        tc = lambda: {"task": state["tasks"],
                      "W": plan_f.put_task(_pad_W(W))}
        acc = _streamed_sum(plan_f, read, n_blocks, tc, stats, sync,
                            restart=restart)
        reg = jax.device_get(plan_reg.fn(zero_dev["b"], tc()))
        return np.asarray(acc["f"])[:L] + np.asarray(reg["f"])[:L]

    w0 = np.zeros((T, width), np.float32)
    if w_init is not None:
        # warm start: lanes begin at the caller's (T, width) seeds
        w0[:] = np.asarray(w_init, np.float32).reshape(T, width)
    tol = np.asarray(task_args["hyper"]["tol"], np.float32).reshape(T)
    unpack = _host_unpack(est_cls, meta, static, dataset)

    pass_hook = None
    if rung_hook is not None:
        def pass_hook(pass_idx, lane_ids, w_rows, it_rows, done_rows):
            live = ~done_rows
            live_ids = lane_ids[live]
            if live_ids.size == 0:
                return np.empty(0, np.int64)
            w_live, it_live = w_rows[live], it_rows[live]

            def make_params():
                return _stack_params([
                    unpack(w_live[i], int(it_live[i]))
                    for i in range(live_ids.size)
                ])

            killed = np.asarray(
                rung_hook(pass_idx, live_ids, make_params), np.int64
            ).reshape(-1)
            if killed.size:
                sel["idx"] = lane_ids[~np.isin(lane_ids, killed)]
                if sel["idx"].size:  # all-killed: no further dispatches
                    place_tasks()
                stats["retired_rung"] = (
                    stats.get("retired_rung", 0) + int(killed.size)
                )
                # counterfactual upper bound: a killed lane would have
                # paid at most (max_iter - pass_idx) more solver passes
                stats["passes_saved"] = (
                    stats.get("passes_saved", 0)
                    + int(killed.size) * max(0, max_iter - pass_idx)
                )
            return killed

    W, n_iter, _done = lbfgs_stream(
        eval_fg, eval_f, w0, tol, max_iter, history=history,
        max_ls=20, pass_hook=pass_hook,
    )
    if rung_hook is not None and sel["idx"].size < T:
        # bytes are shared across lanes per pass: the race ending at
        # max(n_iter) instead of the iteration cap saves whole-dataset
        # passes (an upper-bound estimate, documented as such)
        stats["streamed_bytes_saved"] = (
            stats.get("streamed_bytes_saved", 0)
            + int(dataset.nbytes_estimate)
            * max(0, max_iter - int(n_iter.max(initial=0)))
        )
    params = [unpack(W[t], int(n_iter[t])) for t in range(T)]
    return _stack_params(params)


def _fit_gram_stream(backend, est_cls, meta, static, dataset, row_arrays,
                     task_args, derive, stats, sync, key_extra=(),
                     w_init=None, rung_hook=None):
    """Block-accumulated normal equations for the ridge family: stream
    ``(XᵀSX, XᵀST)`` partials, finish with one solve per task.
    ``w_init`` is accepted and ignored — a direct solve has no
    iterate to seed; ``rung_hook`` likewise — a one-pass direct solve
    has no pass boundaries for a rung to act between (an adaptive
    search over a gram family stays exhaustive and warns)."""
    from ..sparse import LinearOperator
    from .linear import _apply_class_weight, maybe_exact_matmuls

    st = dict(static)
    fit_intercept = st["fit_intercept"]
    d = meta["n_features"]
    k = meta.get("n_classes")
    class_weight = st.get("class_weight")
    cw_arr = meta.get("cw_arr")

    def gram_kernel(block, tc):
        Xb, yb, swb, hyper = derive(block, tc["task"])
        op = LinearOperator(Xb, fit_intercept)
        if k is not None:
            swb = _apply_class_weight(swb, yb, k, class_weight, cw_arr)
            if k <= 2:
                T_t = jnp.where(yb == (k - 1), 1.0, -1.0).astype(
                    op.dtype)[:, None]
            else:
                T_t = jnp.where(
                    jax.nn.one_hot(yb, k) > 0, 1.0, -1.0
                ).astype(op.dtype)
        else:
            T_t = yb.astype(jnp.float32).reshape(yb.shape[0], -1)
        G, b = op.weighted_gram_rhs(swb, T_t)
        return {"G": G, "b": b}

    def finish_kernel(_z, tc):
        G, b = tc["G"], tc["b"]
        alpha = tc["task"]["hyper"].get("alpha", jnp.float32(0.0))
        p = G.shape[0]
        reg = jnp.concatenate([jnp.full((d,), alpha), jnp.zeros(p - d)])
        G = G + jnp.diag(reg)
        G = G + 1e-8 * jnp.eye(p, dtype=G.dtype)
        return {"W": jax.scipy.linalg.solve(G, b, assume_a="pos")}

    gram_kernel = maybe_exact_matmuls(est_cls, gram_kernel)
    finish_kernel = maybe_exact_matmuls(est_cls, finish_kernel)
    example = _example_block(dataset, row_arrays)
    plan = backend.prepare_streamed(
        gram_kernel, example,
        cache_key=_stream_key(est_cls, static, meta, "gram", key_extra),
    )
    plan_fin = backend.prepare_streamed(
        finish_kernel, None,
        cache_key=_stream_key(est_cls, static, meta, "gram_fin", key_extra),
    )
    T = _n_tasks(task_args)
    task_args, _Tp = _slot_pad_tree(task_args, T, plan.n_task_slots)
    read = _make_block_read(dataset, row_arrays, pad=True)
    state = {"tasks": plan.put_task(task_args)}

    def restart():
        _elastic_replans(backend, (plan, plan_fin))
        state["tasks"] = plan.put_task(task_args)
        faults.record("shared_replacements")

    acc = _streamed_sum(
        plan, read, dataset.n_blocks,
        lambda: {"task": state["tasks"]}, stats, sync, restart=restart,
    )
    fin = jax.device_get(plan_fin.fn(
        plan_fin.put_block({"z": np.zeros(1, np.float32)}),
        {
            "task": plan_fin.put_task(task_args),
            "G": jnp.asarray(acc["G"]),
            "b": jnp.asarray(acc["b"]),
        },
    ))
    W = np.asarray(fin["W"])  # (T, p, k_out)
    out = []
    for t in range(T):
        Wt = W[t]
        if k is not None and k <= 2:
            Wt = Wt[:, 0]
        elif k is None and meta.get("y_ndim", 1) == 1:
            Wt = Wt[:, 0]
        out.append({"W": Wt})
    return _stack_params(out)


def _fit_sgd_stream(backend, est_cls, meta, static, dataset, row_arrays,
                    task_args, derive, stats, sync, key_extra=(),
                    w_init=None, rung_hook=None):
    """Epochs as block streams: visit blocks in order, advance the
    mini-batch carry through the resident scan's exact update
    (``solvers.sgd_batch_scan``), apply the epoch-end early-stopping
    bookkeeping host-side in f32 — mirroring ``solvers._sgd_epoch_body``
    value for value, so an aligned, unshuffled streamed fit is bitwise
    identical to the resident kernel. ``rung_hook`` (see
    :func:`stream_fit_tasks`) is consulted at every epoch boundary —
    the SGD rendition of the rung-at-block-pass contract: killed lanes
    record their kill-time carry and compact out of the device batch."""
    from .linear import maybe_exact_matmuls
    from .solvers import sgd_batch_scan

    st = dict(static)
    max_iter = int(st["max_iter"])
    batch_size = int(st["batch_size"])
    n_iter_no_change = int(st["n_iter_no_change"])
    shuffle = bool(st.get("shuffle", True))
    penalty = st["penalty"]
    width = est_cls._flat_w_width(meta, static)
    problem = est_cls._build_fit_problem(meta, static)
    R = dataset.block_rows
    n = dataset.n_rows
    if R % batch_size and dataset.n_blocks > 1:
        raise ValueError(
            f"streamed SGD needs block_rows ({R}) divisible by "
            f"batch_size ({batch_size}) so mini-batches never straddle "
            "blocks; rebuild the ChunkedDataset with an aligned "
            "block_rows"
        )

    def block_kernel(block, tc):
        Xb, yb, swb, hyper = derive(block, tc["task"])
        pb = problem(Xb, yb, swb, hyper)
        rows = yb.shape[0]
        n_b = rows // batch_size
        if shuffle:
            bkey = jax.random.fold_in(
                jax.random.fold_in(pb["key"], block["epoch"]),
                block["bid"],
            )
            perm = jax.random.permutation(bkey, rows)
        else:
            perm = jnp.arange(rows)
        batches = perm.reshape(n_b, batch_size)
        carry = tc["carry"]
        w, pstate, step, acc = sgd_batch_scan(
            pb["grad_fn"], pb["lr_fn"], pb["post_step"], pb["loss_fn"],
            True,
            (carry["w"], carry["pstate"], carry["step"], carry["acc"]),
            batches,
        )
        return {"w": w, "pstate": pstate, "step": step, "acc": acc}

    block_kernel = maybe_exact_matmuls(est_cls, block_kernel)
    example = _example_block(dataset, row_arrays, ("epoch", "bid"))
    plan = backend.prepare_streamed(
        block_kernel, example,
        cache_key=_stream_key(est_cls, static, meta, "sgd", key_extra),
    )

    # ---- epoch plan: full blocks + a virtual tail whose trailing
    # batch wraps to the dataset head (the streamed rendition of the
    # resident scan's arange(padded) % n wrap) -----------------------
    base_read = _make_block_read(dataset, row_arrays, pad=False)
    full_blocks = n // R
    rem = n - full_blocks * R
    if rem == 0 and n % batch_size:
        # every block is full but the epoch still needs a wrap batch
        # (possible only for a single-block dataset — aligned
        # block_rows is enforced above for more): demote the last full
        # block to the virtual tail so the wrap rows get appended
        full_blocks -= 1
        rem = R
    tail_rows = 0
    wrap_tree = None
    if rem:
        tail_rows = int(math.ceil(rem / batch_size) * batch_size)
        wrap = tail_rows - rem
        if wrap:
            # wrap rows are the resident scan's arange(padded) % n
            # tail: global rows (n + j) % n = j % n for j < wrap. When
            # wrap <= n they are simply the dataset head; a dataset
            # SMALLER than one batch cycles (possible only when the
            # whole dataset is the tail block, so block 0 holds every
            # row the cycle can touch)
            head = base_read(0)
            avail = rem if full_blocks == 0 else R
            idx = np.arange(wrap) % min(avail, n)
            wrap_tree = jax.tree_util.tree_map(
                lambda a: np.asarray(a)[idx], head
            )

    def read_epoch_block(e):
        def read(i):
            if rem and i == full_blocks:
                tree = base_read(full_blocks)
                if wrap_tree is not None:
                    tree = jax.tree_util.tree_map(
                        lambda a, w_: np.concatenate(
                            [np.asarray(a), w_]
                        ),
                        tree, wrap_tree,
                    )
            else:
                tree = base_read(i)
            tree["epoch"] = np.int32(e)
            tree["bid"] = np.int32(i)
            return tree

        return read

    n_stream_blocks = full_blocks + (1 if rem else 0)
    n_batches_total = np.float32(-(-n // batch_size))

    T = _n_tasks(task_args)
    tol = np.asarray(task_args["hyper"]["tol"], np.float32).reshape(T)
    if penalty in ("l1", "elasticnet"):
        pstate0 = (np.zeros(T, np.float32),
                   np.zeros((T, width), np.float32))
    else:
        pstate0 = ()
    w0 = np.zeros((T, width), np.float32)
    if w_init is not None:
        # warm start: epochs begin at the caller's (T, width) seeds
        w0[:] = np.asarray(w_init, np.float32).reshape(T, width)

    # the device batch covers the LIVE lane subset (sel["idx"]); host
    # bookkeeping stays full-size, indexed through the lane map. A
    # rung kill records the killed lanes' carry into w_out and
    # compacts the device batch — later epochs stream the same blocks
    # through fewer programs.
    sel = {"idx": np.arange(T)}
    dev = {}

    def place_tasks(fresh=True):
        # ``fresh`` recomputes the padded width from the current slot
        # count; an elastic restart keeps the previous width instead
        # (the largest-divisor re-layout guarantees it still divides)
        # so the epoch-start carry snapshot stays size-consistent.
        L = sel["idx"].size
        if fresh or "Lp" not in sel:
            slots = max(1, int(plan.n_task_slots))
            sel["Lp"] = -(-L // slots) * slots
        dev["tasks"] = plan.put_task(
            _pad_tree_to(_take_tree(task_args, sel["idx"]), L, sel["Lp"])
        )

    def place_carry(host_tree_L):
        return plan.put_task(
            _pad_tree_to(host_tree_L, sel["idx"].size, sel["Lp"])
        )

    place_tasks()
    carry = place_carry({
        "w": w0, "pstate": pstate0,
        "step": np.zeros(T, np.int32),
        "acc": np.zeros(T, np.float32),
    })
    # host-side early-stopping state (mirrors _sgd_epoch_body's tail)
    best = np.full(T, np.inf, np.float32)
    bad = np.zeros(T, np.int64)
    n_done = np.zeros(T, np.int64)
    done = np.zeros(T, bool)
    w_out = w0.copy()
    unpack = _sgd_host_unpack(est_cls, meta, static)

    guard = _BlockRetry(stats)
    epoch_guard = _BlockRetry(stats)
    e = 0
    epochs_run = 0
    while e < max_iter:
        lane = sel["idx"]
        L = lane.size
        carry_start = carry
        # host snapshot of the epoch-start carry: the preemption
        # restart below (and the epoch-retry path) re-place from it
        # (device buffers are presumed lost with the worker)
        host_start = jax.device_get(carry_start)
        carry = _reset_acc(carry)
        read = read_epoch_block(e)
        # late-bound placement: an elastic restart rebuilds `plan` in
        # place mid-epoch and later blocks must land on the new mesh
        feeder = BlockFeeder(read, n_stream_blocks,
                             lambda t: plan.put_block(t),
                             sync=sync, stats=stats)
        try:
            while True:
                item = feeder.next()
                if item is None:
                    break
                i, dv = item
                t0 = time.perf_counter()
                try:
                    _dispatch_seam()
                    carry = plan.fn(dv, {"task": dev["tasks"],
                                         "carry": carry})
                except Exception as exc:
                    def restart():
                        # preemption loses device state: shrink an
                        # elastic mesh to the survivors, re-place the
                        # tasks and rewind to the epoch-start carry
                        nonlocal carry
                        _elastic_replans(backend, (plan,))
                        place_tasks(fresh=False)
                        carry = _reset_acc(plan.put_task(host_start))
                        faults.record("shared_replacements")

                    # a TRANSIENT fault at block i leaves the input
                    # carry (the post-(i-1) state) valid: the feeder
                    # re-opens the reader at block i and the identical
                    # dispatch re-runs bitwise
                    guard.handle(exc, feeder, i, restart=restart)
                    continue
                stats["dispatch_s"] += time.perf_counter() - t0
        finally:
            feeder.close()
        try:
            acc = np.asarray(
                jax.device_get(carry["acc"]), np.float32
            )[:L]
        except Exception as exc:
            # async fault surfacing only at the blocking gather: the
            # whole epoch's carry chain is suspect — re-run the epoch
            # from its start snapshot (deterministic, so bitwise)
            kind = faults.classify(exc)
            if not faults.is_retryable(kind):
                raise
            epoch_guard.retry.admit(_RoundFault([], 0, exc, kind), e)
            stats["retries"] = epoch_guard.retry.total
            if kind == faults.PREEMPTED:
                _elastic_replans(backend, (plan,))
                place_tasks(fresh=False)
                faults.record("shared_replacements")
            carry = plan.put_task(host_start)
            continue
        epochs_run = e + 1
        # ---- epoch-end bookkeeping: the resident epoch body's tail,
        # value for value, in host f32 (same IEEE ops => bitwise) -----
        keep = done[lane]
        loss = (acc / n_batches_total).astype(np.float32)
        improved = loss < (best[lane] - tol[lane]).astype(np.float32)
        bad_new = np.where(improved, 0, bad[lane] + 1)
        newly_stopped = bad_new >= n_iter_no_change
        best_new = np.minimum(best[lane], loss).astype(np.float32)
        if keep.any():
            # frozen lanes keep their epoch-start carry, exactly like
            # the resident scan's pick()
            kmask = _pad_tree_to(keep, L, sel["Lp"])
            carry = _pick_carry(plan.put_task(kmask), carry_start, carry)
        best[lane] = np.where(keep, best[lane], best_new)
        bad[lane] = np.where(keep, bad[lane], bad_new)
        n_done[lane] = np.where(keep, n_done[lane], n_done[lane] + 1)
        done[lane] = keep | newly_stopped | ((e + 1) >= max_iter)
        # ---- rung hook at the epoch (block-pass) boundary ----------
        if rung_hook is not None:
            live = ~done[lane]
            live_ids = lane[live]
            if live_ids.size:
                def make_params():
                    w_h = np.asarray(
                        jax.device_get(carry["w"]), np.float32
                    )[:L][live]
                    return _stack_params([
                        unpack(w_h[i], int(n_done[live_ids[i]]))
                        for i in range(live_ids.size)
                    ])

                killed = np.asarray(
                    rung_hook(e + 1, live_ids, make_params), np.int64
                ).reshape(-1)
                if killed.size:
                    host_c = jax.tree_util.tree_map(
                        lambda a: np.asarray(a)[:L],
                        jax.device_get(carry),
                    )
                    drop = np.isin(lane, killed)
                    w_out[lane[drop]] = np.asarray(
                        host_c["w"], np.float32
                    )[drop]
                    done[killed] = True
                    sel["idx"] = lane[~drop]
                    if sel["idx"].size:
                        place_tasks()
                        carry = place_carry(_take_tree(
                            host_c, np.flatnonzero(~drop)
                        ))
                    stats["retired_rung"] = (
                        stats.get("retired_rung", 0) + int(killed.size)
                    )
                    stats["passes_saved"] = (
                        stats.get("passes_saved", 0)
                        + int(killed.size) * max(0, max_iter - (e + 1))
                    )
        lane_now = sel["idx"]
        if lane_now.size == 0 or done[lane_now].all():
            break
        e += 1

    lane = sel["idx"]
    if lane.size:
        w_out[lane] = np.asarray(
            jax.device_get(carry["w"]), np.float32
        )[: lane.size]
    if rung_hook is not None and lane.size < T:
        stats["streamed_bytes_saved"] = (
            stats.get("streamed_bytes_saved", 0)
            + int(dataset.nbytes_estimate)
            * max(0, max_iter - epochs_run)
        )
    # unpack per task (host reshape, identical to the family unpack)
    params = [unpack(w_out[t], int(n_done[t])) for t in range(T)]
    return _stack_params(params)


def _sgd_host_unpack(est_cls, meta, static):
    st = dict(static)
    p = meta["n_features"] + (1 if st["fit_intercept"] else 0)
    k = meta.get("n_classes", 2)
    n_out = 1 if k <= 2 else k

    def unpack(Wf, n_epochs):
        W = np.asarray(Wf).reshape(p, n_out)
        if n_out == 1:
            W = W[:, 0]
        return {"W": W, "n_iter": n_epochs}

    return unpack


def _reset_acc(carry):
    return {**carry, "acc": jnp.zeros_like(carry["acc"])}


def _pick_carry(keep_dev, old, new):
    """``where(keep, old, new)`` leaf-wise with the (T,) mask broadcast
    to each leaf's rank — the device rendition of the resident epoch
    body's freeze pick."""

    def pick(a, b):
        m = jnp.reshape(keep_dev, keep_dev.shape + (1,) * (a.ndim - 1))
        return jnp.where(m, a, b)

    return jax.tree_util.tree_map(pick, old, new)


def _fit_gbdt_stream(backend, est_cls, meta, static, dataset, row_arrays,
                     task_args, derive, stats, sync, key_extra=(),
                     w_init=None, rung_hook=None):
    """Boosting rounds as binned-cache streams.

    Round structure (all passes read the uint8 binned cache, never raw
    features): per tree level, a histogram pass routes every block's
    rows to their current node with the partial heap placed in the task
    tree, scatters ``newton_channels(grad, hess, w)`` into per-(class,
    feature, node, bin) histograms, and accumulates across blocks
    (:func:`_streamed_sum`; on a mesh the row-sharded scatter psums
    over 'data'). A device chooser then scores the gathered histograms
    with the resident kernel's OWN :func:`~.tree.histogram_node_scores`
    / :func:`~.tree.pick_level_splits` — parity by shared code. The
    host assembles the round's heap (leaf values from the level totals:
    an unsplit node's Newton step is ``−G/(H+λ)`` of the samples
    resting there; last-level children split their parent's totals via
    the recorded left-cumulative stats, exactly the resident kernel's
    final-assignment scatter re-expressed). One update pass advances
    the margin carry ``F`` and accumulates the early-stop monitor.

    ``F`` lives in two host memmaps sized (T, n, Kt): the update pass
    reads ``F_cur`` and writes ``F_nxt``, committing not-yet-done lanes
    back to ``F_cur`` only after the pass completes — so a transient
    fault replays block i bitwise (its input rows are untouched) and a
    preemption rewinds the whole pass idempotently. ``w_init`` is
    accepted and ignored (an ensemble has no flat iterate to seed).

    ``rung_hook`` fires at every round boundary with finalize-shaped
    params for the live lanes (unrun rounds hold all-zero trees, so the
    decision kernel's full static-T scan is exact mid-race); killed
    lanes compact out of the task batch and their F rows go cold."""
    from .gbdt import _P_EPS, _build_boost_parts, _stacked_tree_walk
    from .tree import (
        _NEG, histogram_node_scores, n_tree_nodes, newton_channels,
        pick_level_splits,
    )

    st = dict(static)
    parts = _build_boost_parts(meta, static)
    grads, loss_vals = parts["grads"], parts["loss_vals"]
    Kt, D, K = parts["Kt"], parts["D"], parts["K"]
    classification = parts["classification"]
    max_iter = parts["T"]
    N = n_tree_nodes(D)
    es = bool(st["_early_stopping"])
    patience = int(st["n_iter_no_change"])
    msl = int(st["min_samples_leaf"])
    B = int(st["max_bins"])
    d = int(st["_n_features"])

    cache = meta.get("binned_cache")
    if cache is None:
        cache = dataset.with_binned_cache(
            edges=np.asarray(meta["edges"], np.float32), max_bins=B
        )
    edges_np = np.asarray(meta["edges"], np.float32)
    stats["binned_bytes_cached"] = (
        stats.get("binned_bytes_cached", 0)
        + (0 if cache.hit else int(cache.nbytes))
    )

    n = dataset.n_rows
    R = dataset.block_rows
    n_blocks = dataset.n_blocks
    T = _n_tasks(task_args)
    lr_h = np.asarray(task_args["hyper"]["learning_rate"],
                      np.float32).reshape(T)
    lam_h = np.asarray(task_args["hyper"]["l2_regularization"],
                       np.float32).reshape(T)
    tol_h = np.asarray(task_args["hyper"]["tol"], np.float32).reshape(T)

    # ---- kernels ----------------------------------------------------

    def _routed_nodes(Xb, f_a, t_a, s_a, level):
        # replay `level` levels of heap routing — tree_predict_kernel's
        # walk against the partial heap (non-split nodes carry
        # is_split=False and hold their samples, like the resident
        # level loop's split_s gate)
        node = jnp.zeros(Xb.shape[0], jnp.int32)
        for _ in range(level):
            f = jnp.clip(f_a[node], 0, d - 1)
            t = t_a[node]
            s = s_a[node]
            b = jnp.take_along_axis(Xb, f[:, None], axis=1)[:, 0]
            child = 2 * node + 1 + (b > t).astype(jnp.int32)
            node = jnp.where(s, child, node)
        return node

    def make_hist_kernel(level):
        nl = 2 ** level
        start = nl - 1

        def kernel(block, tc):
            Xb_u, yb, fit_w, _hyper = derive(block, tc["task"])
            Xb = Xb_u.astype(jnp.int32)
            F_lane = block["F"][tc["task"]["lane"]]
            g, h = grads(F_lane, yb)
            tr = tc["task"]["tree"]

            def one_class(gk, hk, f_a, t_a, s_a):
                node = _routed_nodes(Xb, f_a, t_a, s_a, level)
                at = (node >= start) & (node < start + nl)
                rel = jnp.clip(node - start, 0, nl - 1)
                Ych = newton_channels(gk, hk, fit_w) * \
                    at[:, None].astype(jnp.float32)
                seg = (jnp.arange(d)[None, :] * nl + rel[:, None]) * B + Xb
                hist = jnp.zeros((d * nl * B, 3), jnp.float32).at[
                    seg.reshape(-1)
                ].add(jnp.repeat(Ych, d, axis=0))
                return hist.reshape(d, nl, B, 3)

            if Kt == 1:
                hist = one_class(
                    g, h, tr["feat"][0], tr["thr"][0], tr["split"][0]
                )[None]
            else:
                hist = jax.vmap(one_class, in_axes=(1, 1, 0, 0, 0))(
                    g, h, tr["feat"], tr["thr"], tr["split"]
                )
            return {"hist": hist}  # (Kt, d, nl, B, 3)

        return kernel

    def make_choose_kernel(level):
        nl = 2 ** level

        def kernel(_z, tc):
            lam = tc["task"]["hyper"]["l2_regularization"]

            def one_class(hk):
                cum = jnp.cumsum(hk, axis=2)
                gain, cnt_l, cnt_r, tot = histogram_node_scores(
                    cum, lam, newton=True
                )
                node_cnt = tot[0, :, -1]
                ok = (cnt_l >= msl) & (cnt_r >= msl)
                gain = jnp.where(ok, gain, _NEG)
                # w_root=1 is exact here: the boost kernel fixes
                # min_impurity_decrease=0, so the decrease gate reduces
                # to best_gain > 1e-12 for ANY positive root mass —
                # the same decision the resident kernel takes
                best_f, best_t, _bg, do_split = pick_level_splits(
                    gain, node_cnt, min_samples_split=2,
                    w_root=jnp.float32(1.0), min_impurity_decrease=0.0,
                )
                lstat = cum[best_f, jnp.arange(nl), best_t]
                return {"feat": jnp.where(do_split, best_f, -1),
                        "thr": best_t, "split": do_split,
                        "tot": tot[0], "lstat": lstat}

            hist = tc["hist"]
            if Kt == 1:
                return jax.tree_util.tree_map(
                    lambda a: a[None], one_class(hist[0])
                )
            return jax.vmap(one_class)(hist)

        return kernel

    def update_kernel(block, tc):
        Xb_u, yb, fit_w, _hyper = derive(block, tc["task"])
        Xb = Xb_u.astype(jnp.int32)
        F_lane = block["F"][tc["task"]["lane"]]
        tr = tc["task"]["tree"]
        F_new = F_lane + _stacked_tree_walk(
            Xb, tr["feat"], tr["thr"], tr["split"], tr["leaf"], D
        )
        lv = loss_vals(F_new, yb)
        return {"F": F_new, "mon_num": jnp.sum(fit_w * lv),
                "mon_den": jnp.sum(fit_w)}

    def init_kernel(block, tc):
        # per-lane baseline sufficient statistics (fold-masked weights
        # differ per lane): class-weighted counts / weighted y sum
        _Xb, yb, fit_w, _hyper = derive(block, tc["task"])
        if classification:
            s = jax.nn.one_hot(yb, max(K, 2), dtype=jnp.float32).T @ fit_w
        else:
            s = jnp.sum(fit_w * yb.astype(jnp.float32))[None]
        return {"s": s, "w": jnp.sum(fit_w)[None]}

    # ---- plans ------------------------------------------------------
    example = {"X": np.zeros((R, d), np.uint8)}
    for name, arr in row_arrays.items():
        arr = np.asarray(arr)
        example[name] = np.zeros((R,) + arr.shape[1:], arr.dtype)
    example["F"] = np.zeros((1, R, Kt), np.float32)

    def skey(part):
        return _stream_key(est_cls, static, meta, part, key_extra)

    plans_h = [
        backend.prepare_streamed(make_hist_kernel(l), example,
                                 cache_key=skey(f"gbdt_h{l}"))
        for l in range(D)
    ]
    plans_c = [
        backend.prepare_streamed(make_choose_kernel(l), None,
                                 cache_key=skey(f"gbdt_c{l}"))
        for l in range(D)
    ]
    plan_u = backend.prepare_streamed(update_kernel, example,
                                      cache_key=skey("gbdt_u"))
    plan_b = backend.prepare_streamed(init_kernel, example,
                                      cache_key=skey("gbdt_b"))
    all_plans = plans_h + plans_c + [plan_u, plan_b]

    # ---- host state -------------------------------------------------
    sel = {"idx": np.arange(T)}
    state = {}
    fdir = tempfile.mkdtemp(prefix="skdist_gbdt_F_")
    F_cur = np.lib.format.open_memmap(
        os.path.join(fdir, "F_cur.npy"), mode="w+",
        dtype=np.float32, shape=(T, n, Kt),
    )
    F_nxt = np.lib.format.open_memmap(
        os.path.join(fdir, "F_nxt.npy"), mode="w+",
        dtype=np.float32, shape=(T, n, Kt),
    )

    def read(i):
        s0 = i * R
        e0 = min(s0 + R, n)
        m = e0 - s0
        xb = np.zeros((R, d), np.uint8)
        xb[:m] = cache.xb[s0:e0]
        tree = {"X": xb}
        for name, arr in row_arrays.items():
            sl = np.asarray(arr[s0:e0])
            if m < R:
                sl = np.concatenate([
                    sl,
                    np.full((R - m,) + sl.shape[1:],
                            _pad_rows_for(name), sl.dtype),
                ])
            tree[name] = sl
        idx = sel["idx"]
        L = idx.size
        Fp = np.zeros((state["Lp"], R, Kt), np.float32)
        Fp[:L, :m] = F_cur[idx, s0:e0]
        if state["Lp"] > L:
            Fp[L:] = Fp[L - 1]  # duplicate-last, like _pad_tree_to
        tree["F"] = Fp
        return tree

    def place_current():
        state["tasks"] = plan_u.put_task(state["task_host"])

    def place_round(tree_host):
        L = sel["idx"].size
        if "Lp" not in state:
            slots = max(1, int(plan_u.n_task_slots))
            state["Lp"] = -(-L // slots) * slots
        th = dict(_take_tree(task_args, sel["idx"]))
        th["lane"] = np.arange(L, dtype=np.int32)
        th["tree"] = tree_host
        state["task_host"] = _pad_tree_to(th, L, state["Lp"])
        place_current()

    def restart_pass():
        _elastic_replans(backend, all_plans)
        place_current()
        faults.record("shared_replacements")

    def tc():
        return {"task": state["tasks"]}

    try:
        # ---- baseline pass ------------------------------------------
        zero_heap = {
            "feat": np.full((T, Kt, N), -1, np.int32),
            "thr": np.zeros((T, Kt, N), np.int32),
            "split": np.zeros((T, Kt, N), bool),
        }
        place_round(zero_heap)
        acc0 = _streamed_sum(plan_b, read, n_blocks, tc, stats, sync,
                             restart=restart_pass)
        stats["binned_bytes_streamed"] += int(cache.nbytes)
        sS = np.asarray(acc0["s"], np.float32)[:T]
        wS = np.maximum(np.asarray(acc0["w"], np.float32)[:T, 0],
                        np.float32(1e-12))
        if not classification:
            base_all = (sS[:, :1] / wS[:, None]).astype(np.float32)
        elif K <= 2:
            p = np.clip(sS[:, K - 1] / wS, _P_EPS,
                        np.float32(1.0) - np.float32(_P_EPS))
            base_all = np.log(
                p / (np.float32(1.0) - p)
            ).astype(np.float32)[:, None]
        else:
            pri = sS / wS[:, None]
            base_all = np.log(
                np.clip(pri, _P_EPS, None)
            ).astype(np.float32)
        for t in range(T):
            F_cur[t] = base_all[t][None, :]

        # ---- outputs + early-stop mirrors (resident carry, host f32)
        feat_all = np.full((T, max_iter, Kt, N), -1, np.int32)
        thr_all = np.zeros((T, max_iter, Kt, N), np.int32)
        split_all = np.zeros((T, max_iter, Kt, N), bool)
        leaf_all = np.zeros((T, max_iter, Kt, N), np.float32)
        best = np.full(T, np.inf, np.float32)
        bad = np.zeros(T, np.int64)
        n_rounds = np.zeros(T, np.int64)
        done = np.zeros(T, bool)

        guard = _BlockRetry(stats)
        r = 0
        rounds_run = 0
        while r < max_iter:
            lane = sel["idx"]
            L = lane.size
            # ---- grow one tree per live lane, level by level --------
            featH = np.full((L, Kt, N), -1, np.int32)
            thrH = np.zeros((L, Kt, N), np.int32)
            splitH = np.zeros((L, Kt, N), bool)
            tots, lstats = [], []
            for l in range(D):
                place_round({"feat": featH, "thr": thrH,
                             "split": splitH})
                acc = _streamed_sum(plans_h[l], read, n_blocks, tc,
                                    stats, sync, restart=restart_pass)
                stats["binned_bytes_streamed"] += int(cache.nbytes)
                fin = jax.device_get(plans_c[l].fn(
                    plans_c[l].put_block({"z": np.zeros(1, np.float32)}),
                    {"task": state["tasks"],
                     "hist": jnp.asarray(acc["hist"])},
                ))
                nl = 2 ** l
                i0 = nl - 1
                featH[:, :, i0:i0 + nl] = np.asarray(
                    fin["feat"], np.int32)[:L]
                thrH[:, :, i0:i0 + nl] = np.asarray(
                    fin["thr"], np.int32)[:L]
                splitH[:, :, i0:i0 + nl] = np.asarray(
                    fin["split"], bool)[:L]
                tots.append(np.asarray(fin["tot"], np.float32)[:L])
                lstats.append(np.asarray(fin["lstat"], np.float32)[:L])
            # ---- leaves from the level totals (host f32) ------------
            leafH = np.zeros((L, Kt, N), np.float32)
            lam_l = lam_h[lane][:, None, None]
            for l in range(D):
                nl = 2 ** l
                i0 = nl - 1
                tot = tots[l]
                val = -tot[..., 0] / np.maximum(
                    tot[..., 1] + lam_l, np.float32(1e-12)
                )
                leafH[:, :, i0:i0 + nl] = np.where(
                    splitH[:, :, i0:i0 + nl], np.float32(0.0),
                    val.astype(np.float32),
                )
            nl = 2 ** (D - 1)
            i0 = nl - 1
            left = lstats[D - 1]
            right = tots[D - 1] - left
            spD = splitH[:, :, i0:i0 + nl]
            lv = -left[..., 0] / np.maximum(
                left[..., 1] + lam_l, np.float32(1e-12))
            rv = -right[..., 0] / np.maximum(
                right[..., 1] + lam_l, np.float32(1e-12))
            iD = 2 ** D - 1
            leafH[:, :, iD:iD + 2 * nl:2] = np.where(
                spD, lv.astype(np.float32), np.float32(0.0))
            leafH[:, :, iD + 1:iD + 2 * nl:2] = np.where(
                spD, rv.astype(np.float32), np.float32(0.0))
            leafH *= lr_h[lane][:, None, None]

            # ---- update pass: advance F, accumulate the monitor -----
            place_round({"feat": featH, "thr": thrH, "split": splitH,
                         "leaf": leafH})
            num = np.zeros(L, np.float32)
            den = np.zeros(L, np.float32)
            feeder = BlockFeeder(read, n_blocks,
                                 lambda t_: plan_u.put_block(t_),
                                 sync=sync, stats=stats)
            try:
                while True:
                    item = feeder.next()
                    if item is None:
                        break
                    i, dv = item
                    t0 = time.perf_counter()
                    try:
                        _dispatch_seam()
                        out = jax.device_get(plan_u.fn(dv, tc()))
                    except Exception as exc:
                        def restart_u():
                            restart_pass()
                            num[:] = np.float32(0.0)
                            den[:] = np.float32(0.0)

                        # transient: F_cur rows are untouched until the
                        # pass commits, so block i replays bitwise
                        guard.handle(exc, feeder, i, restart=restart_u)
                        continue
                    stats["dispatch_s"] += time.perf_counter() - t0
                    s0 = i * R
                    e0 = min(s0 + R, n)
                    F_nxt[lane, s0:e0] = np.asarray(
                        out["F"], np.float32)[:L, :e0 - s0]
                    num += np.asarray(out["mon_num"], np.float32)[:L]
                    den += np.asarray(out["mon_den"], np.float32)[:L]
            finally:
                feeder.close()
            stats["binned_bytes_streamed"] += int(cache.nbytes)
            rounds_run = r + 1

            # ---- commit F for lanes not yet frozen (block-wise: the
            # carries are memmaps and must not materialise whole) -----
            keep = done[lane]
            upd = lane[~keep]
            for i in range(n_blocks):
                s0 = i * R
                e0 = min(s0 + R, n)
                F_cur[upd, s0:e0] = F_nxt[upd, s0:e0]

            # ---- round-end bookkeeping: the resident round body's
            # tail, value for value, in host f32 ----------------------
            mon = (num / np.maximum(den, np.float32(1e-12))).astype(
                np.float32)
            improved = mon < (best[lane] - tol_h[lane]).astype(np.float32)
            bad_new = np.where(improved, 0, bad[lane] + 1)
            done_new = np.full(L, (r + 1) >= max_iter)
            if es:
                done_new = done_new | (bad_new >= patience)
            act = ~keep
            ai = lane[act]
            feat_all[ai, r] = featH[act]
            thr_all[ai, r] = thrH[act]
            split_all[ai, r] = splitH[act]
            leaf_all[ai, r] = leafH[act]
            best[lane] = np.where(keep, best[lane],
                                  np.minimum(best[lane], mon))
            bad[lane] = np.where(keep, bad[lane], bad_new)
            n_rounds[lane] = np.where(keep, n_rounds[lane], r + 1)
            done[lane] = keep | done_new

            # ---- rung hook at the round (block-pass) boundary -------
            if rung_hook is not None:
                live_ids = lane[~done[lane]]
                if live_ids.size:
                    def make_params():
                        idx = live_ids
                        return {
                            "feat": feat_all[idx], "thr": thr_all[idx],
                            "is_split": split_all[idx],
                            "leaf": leaf_all[idx],
                            "baseline": base_all[idx],
                            "n_iter": n_rounds[idx].astype(np.int32),
                            "edges": np.repeat(
                                edges_np[None], idx.size, axis=0),
                        }

                    killed = np.asarray(
                        rung_hook(r + 1, live_ids, make_params), np.int64
                    ).reshape(-1)
                    if killed.size:
                        # out arrays already hold kill-time params
                        done[killed] = True
                        sel["idx"] = lane[~np.isin(lane, killed)]
                        state.pop("Lp", None)
                        stats["retired_rung"] = (
                            stats.get("retired_rung", 0)
                            + int(killed.size)
                        )
                        stats["passes_saved"] = (
                            stats.get("passes_saved", 0)
                            + int(killed.size) * (D + 1)
                            * max(0, max_iter - (r + 1))
                        )
            lane_now = sel["idx"]
            if lane_now.size == 0 or done[lane_now].all():
                break
            r += 1
    finally:
        del F_cur, F_nxt
        shutil.rmtree(fdir, ignore_errors=True)

    if rung_hook is not None and sel["idx"].size < T:
        # upper bound: every remaining round was D hist passes + one
        # update pass over the cache
        stats["streamed_bytes_saved"] = (
            stats.get("streamed_bytes_saved", 0)
            + int(cache.nbytes) * (D + 1) * max(0, max_iter - rounds_run)
        )
    return {
        "feat": feat_all, "thr": thr_all, "is_split": split_all,
        "leaf": leaf_all, "baseline": base_all,
        "n_iter": n_rounds.astype(np.int32),
        "edges": np.repeat(edges_np[None], T, axis=0),
    }


def _stack_params(params_list):
    """List of per-task param dicts -> dict of stacked (T, ...) arrays
    (n_iter-style scalars stack to (T,))."""
    out = {}
    for key in params_list[0]:
        out[key] = np.stack([
            np.asarray(p[key]) for p in params_list
        ])
    return out


def stream_fit_tasks(backend, est_cls, meta, static, dataset, row_arrays,
                     task_args, derive=None, sync=None, stats=None,
                     key_extra=(), w_init=None, rung_hook=None):
    """Fit a batch of tasks over a ChunkedDataset with the family's
    streamed driver. ``row_arrays`` maps per-row vector names (``y``
    encoded labels, ``sw`` weights, ``fold`` CV fold ids, ...) to
    ``(n_rows,)`` host arrays sliced per block; ``derive(block, task)
    -> (Xb, yb, swb, hyper)`` adapts a placed block + one task lane to
    the family's fit problem (fold masking, OvR binarisation).
    ``w_init`` (``(T, width)`` flat-layout seeds) warm-starts the
    iterative drivers' solver carries (the gram driver's direct solve
    ignores it).

    ``rung_hook(pass_idx, live_ids, make_params) -> killed lane ids``
    is the streamed ASHA seam: the iterative drivers call it at every
    block-pass boundary (an L-BFGS iteration, an SGD epoch) with the
    not-yet-converged lane ids and a zero-arg ``make_params`` closure
    materialising those lanes' CURRENT fitted params (for a
    sufficient-statistics scoring pass over the already-resident
    blocks, :func:`stream_scores`). Lanes it returns are recorded at
    their kill-time iterate and compacted out of the device batch —
    retired lanes stop paying device FLOPs and their task-tree slots
    compact away. The gram driver has no pass boundaries and ignores
    the hook. Returns a dict of stacked ``(T, ...)`` fitted params
    (killed lanes carry their kill-time params)."""
    kind = getattr(est_cls, "_stream_fit_kind", None)
    if kind is None:
        raise TypeError(
            f"{est_cls.__name__} has no out-of-core fit path "
            "(_stream_fit_kind is unset); materialise the dataset or "
            "use a family with a streamed driver (the linear families "
            "or DistHistGradientBoosting*)"
        )
    _check_data_axis_geometry(backend, dataset)
    sync = _resolve_sync(backend, sync)
    if stats is None:
        stats = _stream_stats(backend, sync)
    derive = derive or _default_derive
    driver = {
        "lbfgs": _fit_lbfgs_stream,
        "sgd": _fit_sgd_stream,
        "gram": _fit_gram_stream,
        "gbdt": _fit_gbdt_stream,
    }[kind]
    stats["tasks"] = stats.get("tasks", 0) + _n_tasks(task_args)
    out = driver(backend, est_cls, meta, static, dataset, row_arrays,
                 task_args, derive, stats, sync, key_extra=key_extra,
                 w_init=w_init, rung_hook=rung_hook)
    # delta-publication (publish_round_stats): safe on a shared/
    # re-published dict — the CV driver hands this same dict to
    # stream_scores, whose own publish folds only the scoring pass
    obs_metrics.publish_round_stats(stats)
    return out


# ---------------------------------------------------------------------------
# streamed scoring
# ---------------------------------------------------------------------------

def stream_scores(backend, est_cls, meta, static, dataset, row_arrays,
                  task_args, params, scorer_specs, weight_fns,
                  sync=None, stats=None, key_extra=()):
    """Evaluate fitted per-task params over the dataset with
    decomposable device scorers (``metrics.STREAM_SCORERS``): one
    streamed pass accumulates each metric's sufficient statistics per
    task, host ``combine`` finishes. ``weight_fns`` maps an output
    prefix ('test', 'train') to ``fn(block, task) -> (rows,) weights``.
    Returns ``{f"{prefix}_{name}": (T,) float64}``."""
    from .linear import maybe_exact_matmuls
    from ..metrics import STREAM_SCORERS

    _check_data_axis_geometry(backend, dataset)
    sync = _resolve_sync(backend, sync)
    if stats is None:
        # continue the fit's dict when one exists (the CV driver's
        # contract) — else a fresh schema-complete dict, NOT a bare {}
        # (the feed/dispatch accounting below += into required keys)
        stats = (backend.last_round_stats
                 or obs_metrics.new_round_stats("streamed_scores"))
    decision_kernel = maybe_exact_matmuls(
        est_cls, est_cls._build_decision_kernel(meta, static)
    )
    needs_proba = any(
        STREAM_SCORERS[m][2] == "proba" for _n, m in scorer_specs
    )
    proba_kernel = (
        maybe_exact_matmuls(est_cls, est_cls._build_proba_kernel(meta, static))
        if needs_proba else None
    )

    def score_kernel(block, tc):
        Xb = block["X"]
        yb = block["y"]
        dec = decision_kernel(tc["params"], Xb)
        outputs = {"decision": dec, "predict": dec}
        if proba_kernel is not None:
            outputs["proba"] = proba_kernel(tc["params"], Xb)
        out = {}
        for prefix, wfn in weight_fns.items():
            wv = wfn(block, tc["task"])
            for name, metric in scorer_specs:
                kernel, _combine, kind = STREAM_SCORERS[metric]
                out[f"{prefix}_{name}"] = kernel(
                    yb, outputs[kind], wv, meta
                )
        return out

    score_kernel = maybe_exact_matmuls(est_cls, score_kernel)
    example = _example_block(dataset, row_arrays)
    plan = backend.prepare_streamed(
        score_kernel, example,
        cache_key=_stream_key(est_cls, static, meta, "score",
                              tuple(sorted(
                                  (p, n, m) for p in weight_fns
                                  for n, m in scorer_specs
                              )) + tuple(key_extra)),
    )
    T = _n_tasks(task_args)
    task_args, _Tp = _slot_pad_tree(task_args, T, plan.n_task_slots)
    params, _Tp = _slot_pad_tree(params, T, plan.n_task_slots)
    read = _make_block_read(dataset, row_arrays, pad=True)
    state = {"tc": {"task": plan.put_task(task_args),
                    "params": plan.put_task(params)}}

    def restart():
        # preemption mid-scoring: same contract as the fit passes —
        # elastic shrink + re-place the task/param trees
        _elastic_replans(backend, (plan,))
        state["tc"] = {"task": plan.put_task(task_args),
                       "params": plan.put_task(params)}
        faults.record("shared_replacements")

    acc = _streamed_sum(plan, read, dataset.n_blocks,
                        lambda: state["tc"], stats, sync,
                        restart=restart)
    obs_metrics.publish_round_stats(stats)  # delta of the scoring pass
    out = {}
    for key, parts in acc.items():
        prefix, name = key.split("_", 1)
        metric = dict(scorer_specs)[name]
        _kernel, combine, _kind = STREAM_SCORERS[metric]
        out[key] = np.asarray([
            combine(jax.tree_util.tree_map(
                lambda a, t=t: np.asarray(a)[t], parts
            ), meta)
            for t in range(T)
        ], dtype=np.float64)
    return out


# ---------------------------------------------------------------------------
# single-estimator entry point
# ---------------------------------------------------------------------------

def stream_fit_estimator(est, dataset, y=None, sample_weight=None,
                         backend=None, coef_init=None,
                         intercept_init=None):
    """``estimator.fit(ChunkedDataset)``: the out-of-core fit of one
    estimator — labels/weights from the dataset (or passed explicitly),
    blocks streamed through the double-buffered pipeline, fitted state
    set exactly like a resident fit. ``coef_init``/``intercept_init``
    (sklearn shapes) warm-start the iterative families' solver
    carries — the catalog refresh loop's streamed warm-refit seam."""
    from ..parallel import resolve_backend
    from .linear import _freeze, hyper_float

    if getattr(est, "engine", None) == "host":
        raise ValueError(
            "engine='host' cannot fit a ChunkedDataset: the f64 BLAS "
            "host engine needs X resident. Use engine='auto'/'xla' for "
            "the streamed XLA path."
        )
    backend = resolve_backend(backend)
    if y is None:
        y = dataset.load_y()
    if sample_weight is None:
        sample_weight = dataset.load_sw()
    y_enc, sw, meta = est._prep_stream_fit(dataset, y, sample_weight)
    static_cfg = est._static_config(meta)
    static = _freeze(static_cfg)
    est_cls = type(est)
    task_args = {"hyper": {
        name: np.asarray([hyper_float(getattr(est, name))], np.float32)
        for name in est_cls._hyper_names
    }}
    if "alpha" not in task_args["hyper"] and \
            getattr(est, "alpha", None) is not None and \
            est._stream_fit_kind == "gram":
        task_args["hyper"]["alpha"] = np.asarray(
            [hyper_float(est.alpha)], np.float32
        )
    row_arrays = {"y": y_enc, "sw": sw}
    w_init = None
    if coef_init is not None or intercept_init is not None:
        k = meta.get("n_classes", 2)
        w_init = est._warm_w0_flat(
            meta["n_features"], 1 if k <= 2 else k,
            coef_init, intercept_init,
        )[None]
    params = stream_fit_tasks(
        backend, est_cls, meta, static, dataset, row_arrays, task_args,
        w_init=w_init,
    )
    est._set_fitted(
        {k: np.asarray(v)[0] for k, v in params.items()}, meta
    )
    return est
