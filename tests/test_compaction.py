"""
Convergence-compacted execution tests: iteration-sliced solvers,
live-task compaction in the backend, and cost-ordered round packing.

Pins the PR's contracts:
- a sliced solver run is BITWISE identical to the unsliced solve (both
  solvers, several slice sizes including slice=1 and slice >= max_iter);
- the compacted scheduler path produces the same cv_results_ rows (order
  and values) as the classic fused path and the generic per-task path;
- a forced RESOURCE_EXHAUSTED mid-loop downgrades to the classic path
  with correct results (OOM-resume contract);
- the flags-only slice loop never triggers a recompile after warmup
  (compile_cache counters: misses bounded by kernels x chunk shapes).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from skdist_tpu.models.solvers import (
    lbfgs_carry_init,
    lbfgs_minimize,
    lbfgs_resume,
    sgd_carry_init,
    sgd_minimize,
    sgd_resume,
)
from skdist_tpu.parallel import (
    IterativeKernelSpec,
    LocalBackend,
    TPUBackend,
    compile_cache,
    iterative_fit_supported,
)


# ---------------------------------------------------------------------------
# sliced-vs-unsliced solver bitwise fuzz
# ---------------------------------------------------------------------------

def _logreg_loss(X, y, reg):
    def loss(w):
        z = X @ w
        return jnp.sum(jax.nn.softplus(z) - y * z) + reg * jnp.dot(w, w)

    return loss


@pytest.mark.parametrize("n_slice", [1, 3, 7, 33, 50])
def test_lbfgs_sliced_bitwise(n_slice):
    """Chained short resumes == one unsliced solve, bit for bit, for
    several random problems (incl. slice=1 and slice >= max_iter)."""
    max_iter, tol = 33, 1e-5
    for seed in range(3):
        rng = np.random.RandomState(seed)
        X = jnp.asarray(rng.normal(size=(48, 7)).astype(np.float32))
        y = jnp.asarray((rng.rand(48) > 0.5).astype(np.float32))
        loss = _logreg_loss(X, y, 0.05)
        w0 = jnp.zeros(7, jnp.float32)
        w_ref, it_ref = jax.jit(
            lambda w0: lbfgs_minimize(loss, w0, max_iter, tol)
        )(w0)
        carry = jax.jit(
            lambda w0: lbfgs_carry_init(loss, w0, max_iter, tol)
        )(w0)
        step = jax.jit(
            lambda c: lbfgs_resume(loss, c, n_slice, max_iter, tol)
        )
        for _ in range(200):
            if bool(carry["done"]):
                break
            carry = step(carry)
        assert bool(carry["done"])
        np.testing.assert_array_equal(
            np.asarray(w_ref), np.asarray(carry["w"])
        )
        assert int(it_ref) == int(carry["it"])


@pytest.mark.parametrize("n_slice", [1, 4, 19, 30])
def test_sgd_sliced_bitwise(n_slice):
    max_epochs, batch = 19, 16
    for seed in range(2):
        rng = np.random.RandomState(seed)
        n = 64
        X = jnp.asarray(rng.normal(size=(n, 5)).astype(np.float32))
        y = jnp.asarray((rng.rand(n) > 0.5).astype(np.float32))
        key = jax.random.PRNGKey(seed)

        def grad_fn(w, idx):
            z = X[idx] @ w
            return (
                X[idx].T @ (jax.nn.sigmoid(z) - y[idx]) / idx.shape[0]
                + 0.01 * w
            )

        def loss_fn(w, idx):
            z = X[idx] @ w
            return jnp.mean(jax.nn.softplus(z) - y[idx] * z)

        def lr_fn(t):
            return 0.2 / (1.0 + 0.02 * t)

        w0 = jnp.zeros(5, jnp.float32)
        w_ref, nd_ref = jax.jit(lambda w0: sgd_minimize(
            grad_fn, w0, n, key, max_epochs, batch, lr_fn,
            loss_fn=loss_fn, tol=1e-3,
        ))(w0)
        carry = sgd_carry_init(w0)
        step = jax.jit(lambda c: sgd_resume(
            grad_fn, c, n_slice, n, key, max_epochs, batch, lr_fn,
            loss_fn=loss_fn, tol=1e-3,
        ))
        for _ in range(100):
            if bool(carry["done"]):
                break
            carry = step(carry)
        assert bool(carry["done"])
        np.testing.assert_array_equal(
            np.asarray(w_ref), np.asarray(carry["w"])
        )
        assert int(nd_ref) == int(carry["n_done"])


def test_sliced_vmapped_bitwise():
    """The vmapped (fan-out) shape: a batch of lanes compacts per-lane
    done flags; the final batch of weights must equal the unsliced
    vmapped solve bit for bit."""
    rng = np.random.RandomState(0)
    X = jnp.asarray(rng.normal(size=(48, 7)).astype(np.float32))
    y = jnp.asarray((rng.rand(48) > 0.5).astype(np.float32))
    Cs = jnp.asarray(np.logspace(-2, 2, 9).astype(np.float32))
    max_iter, tol = 25, 1e-5
    w0 = jnp.zeros(7, jnp.float32)

    def fit(C):
        return lbfgs_minimize(
            _logreg_loss(X, y, 0.5 / C), w0, max_iter, tol
        )

    W_ref, it_ref = jax.jit(jax.vmap(fit))(Cs)

    def init(C):
        return lbfgs_carry_init(
            _logreg_loss(X, y, 0.5 / C), w0, max_iter, tol
        )

    def step(C, c):
        return lbfgs_resume(
            _logreg_loss(X, y, 0.5 / C), c, 4, max_iter, tol
        )

    carry = jax.jit(jax.vmap(init))(Cs)
    stepv = jax.jit(jax.vmap(step))
    for _ in range(20):
        if bool(jnp.all(carry["done"])):
            break
        carry = stepv(Cs, carry)
    np.testing.assert_array_equal(np.asarray(W_ref), np.asarray(carry["w"]))
    np.testing.assert_array_equal(
        np.asarray(it_ref), np.asarray(carry["it"])
    )


# ---------------------------------------------------------------------------
# backend: batched_map_iterative
# ---------------------------------------------------------------------------

def _toy_spec_and_tasks(n_tasks=37, n_features=6):
    """A self-contained iterative kernel + its classic fallback over a
    tiny logistic problem, for driving the backend loop directly."""
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.models.linear import _freeze, as_dense_f32

    rng = np.random.RandomState(0)
    X = rng.normal(size=(90, n_features)).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.normal(size=90) > 0).astype(np.int64)
    est = LogisticRegression(max_iter=40, tol=1e-5, engine="xla")
    data, meta = est._prep_fit_data(as_dense_f32(X), y, None)
    static = _freeze(est._static_config(meta))
    plain = type(est)._build_fit_kernel(meta, static)
    ks = type(est)._build_fit_slice_kernels(meta, static, 5)

    def derive(shared, task):
        return (shared["X"], shared["y"], shared["sw"],
                {"C": task["C"], "tol": task["tol"]}, None)

    def init(shared, task):
        return ks["init"](*derive(shared, task)[:4])

    def step(shared, task, carry):
        Xs, ys, sw, hyper, _ = derive(shared, task)
        return ks["step"](Xs, ys, sw, hyper, carry)

    def fin(shared, task, carry):
        Xs, ys, sw, hyper, _ = derive(shared, task)
        return ks["finalize"](Xs, ys, sw, hyper, carry)

    def fallback(shared, task):
        Xs, ys, sw, hyper, _ = derive(shared, task)
        return plain(Xs, ys, sw, hyper)

    spec = IterativeKernelSpec(
        init, step, fin, ks["finalize_keys"], fallback=fallback,
    )
    shared = {"X": np.asarray(data["X"]), "y": np.asarray(data["y"]),
              "sw": np.asarray(data["sw"])}
    tasks = {
        "C": np.logspace(-3, 2, n_tasks).astype(np.float32),
        "tol": np.where(
            np.arange(n_tasks) % 2 == 0, 1e-2, 1e-5
        ).astype(np.float32),
    }
    return spec, fallback, shared, tasks


@pytest.mark.parametrize("make_backend", [TPUBackend, LocalBackend])
def test_iterative_bitwise_at_equal_chunk(make_backend):
    """At the SAME round size, the compacted slice loop's outputs are
    bitwise identical to the classic fused dispatch — compaction only
    changes where the host observes the carry."""
    spec, fallback, shared, tasks = _toy_spec_and_tasks()
    bk = make_backend()
    ref = bk.batched_map(
        fallback, tasks, shared, round_size=8,
        cache_key=("tc", "classic", make_backend.__name__),
    )
    out = bk.batched_map_iterative(
        spec, tasks, shared, round_size=8,
        cache_key=("tc", "iter", make_backend.__name__),
    )
    stats = bk.last_round_stats
    assert stats["mode"] == "compacted"
    assert stats["slices"] >= 2
    assert sum(stats["retired_per_slice"]) == 37
    np.testing.assert_array_equal(ref["W"], out["W"])
    np.testing.assert_array_equal(ref["n_iter"], out["n_iter"])


def test_iterative_compacts_rounds(tpu_backend):
    """On a convergence-skewed task set the round count must shrink as
    lanes retire (the whole point of live-task compaction)."""
    spec, _fallback, shared, tasks = _toy_spec_and_tasks()
    # rounds of 8, stated (the rule's own answer is
    # test_round_size_rule's matter): this reuses the programs
    # test_iterative_bitwise_at_equal_chunk compiled
    tpu_backend.batched_map_iterative(
        spec, tasks, shared, round_size=8,
        cache_key=("tc", "iter", "TPUBackend"),
    )
    stats = tpu_backend.last_round_stats
    rps = stats["rounds_per_slice"]
    assert stats["compactions"] >= 1
    assert rps[-1] < rps[0]
    assert sum(stats["retired_per_slice"]) == 37


# the cell of the benchmark: 50 fits over a 3.2 GB matrix, a lane 3.5 MB
_WIDE = dict(shared_bytes=3_219_200_000, lane_bytes=3_552_302)


@pytest.mark.parametrize("n_tasks, n_slots, sizes, want", [
    # a wide shared operand under few small lanes: one read for all
    (50, 1, _WIDE, (50, "all_tasks")),
    (50, 4, _WIDE, (52, "all_tasks")),
    (3, 8, {}, (8, "all_tasks")),
    # ... under many: as many lanes as weigh what the operand weighs
    # (907), in rounds evenly filled
    (2000, 1, _WIDE, (667, "amortised")),
    (2000, 8, _WIDE, (2000, "all_tasks")),  # every slot reads its copy
    # lanes whose own bytes dominate: about eight rounds, value for
    # value what the rule gave before it read any bytes
    (37, 8, {}, (8, "target_rounds")),
    (480, 1, {}, (60, "target_rounds")),
    (480, 1, dict(shared_bytes=185_911_648, lane_bytes=20_976_766),
     (60, "target_rounds")),
    (480, 8, dict(shared_bytes=185_911_648, lane_bytes=20_976_766),
     (72, "amortised")),  # 9 lanes a slot weigh the matrix; 8 would merge
    (24, 1, dict(shared_bytes=1000, lane_bytes=4_000_000),
     (3, "target_rounds")),
    # a memory cap under all of these: slot-aligned rounds below it
    (50, 1, dict(_WIDE, lanes_fit=20), (17, "memory")),
    (50, 4, dict(_WIDE, lanes_fit=22), (20, "memory")),
    (480, 1, dict(lanes_fit=33), (32, "memory")),
    (480, 8, dict(lanes_fit=3), (8, "memory")),
    # ... and a cap that does not bind leaves the answer alone
    (50, 4, dict(_WIDE, lanes_fit=1237), (52, "all_tasks")),
    (480, 1, dict(lanes_fit=383), (60, "target_rounds")),
])
def test_round_size_rule(n_tasks, n_slots, sizes, want):
    """``iterative_chunk_size``: a round as wide as amortises the read
    of the shared operands, capped by memory, and otherwise the eight
    rounds compaction merges (ISSUE 27)."""
    from skdist_tpu.parallel import iterative_chunk_size
    from skdist_tpu.parallel.backend import _iterative_chunk

    chunk, basis = _iterative_chunk(
        n_tasks, n_slots, sizes.get("shared_bytes", 0),
        sizes.get("lane_bytes", 0), sizes.get("lanes_fit"))
    assert (chunk, basis) == want
    assert chunk % n_slots == 0
    assert iterative_chunk_size(n_tasks, n_slots, **sizes) == chunk
    # the old rule, where no bytes are read
    assert iterative_chunk_size(n_tasks, n_slots) == int(
        np.ceil(max(n_slots, -(-n_tasks // 8)) / n_slots) * n_slots)


@pytest.mark.parametrize("how, want", [
    # 37 lanes over 90 x 48 floats; a lane's carry and what its program
    # holds at its fullest are about half the shared 18 KB: two lanes a
    # slot weigh it, which on eight slots passes the eight-round answer
    # (8) and on one slot does not (5)
    ("rule", {8: (16, "amortised", None), 1: (5, "target_rounds", None)}),
    ("round_size", {8: (24, "round_size", None),
                    1: (20, "round_size", None)}),
    # a device that says what it has free: the cap is booked, and binds
    # where the program's footprint passes it
    ("roomy", {8: (16, "amortised", "some"),
               1: (5, "target_rounds", "some")}),
    ("tight", {8: (8, "memory", 8), 1: (1, "memory", 1)}),
])
def test_backend_books_round_sizing(how, want, monkeypatch):
    """Both backends size a compacted dispatch through
    ``_size_iterative_round`` and book ``chunk_basis`` / ``lanes_fit``
    beside ``chunk``; the answers do not depend on the round size
    beyond f32 noise."""
    spec, _fallback, shared, tasks = _toy_spec_and_tasks(n_features=47)
    ref = None
    for make_backend in (TPUBackend, LocalBackend):
        bk = make_backend()
        if how in ("roomy", "tight"):
            monkeypatch.setattr(
                bk, "_free_device_bytes",
                lambda: 1 << 30 if how == "roomy" else 4096,
                raising=False)
        out = bk.batched_map_iterative(
            spec, tasks, shared,
            round_size=20 if how == "round_size" else None,
            # a key of this toy's own: the kernels memoised under
            # ("tc", ...) were built for the six-column toy
            cache_key=("tc47", "iter", make_backend.__name__),
        )
        stats = bk.last_round_stats
        chunk, basis, fit = want[bk.n_task_slots]
        assert stats["mode"] == "compacted"
        assert (stats["chunk"], stats["chunk_basis"]) == (chunk, basis)
        if fit == "some":
            assert stats["lanes_fit"] > 37
        else:
            assert stats["lanes_fit"] == fit
        if ref is None:
            ref = out
        np.testing.assert_allclose(ref["W"], out["W"], atol=1e-5)


def test_iterative_oom_falls_back_to_classic(monkeypatch):
    """A RESOURCE_EXHAUSTED inside the slice loop downgrades to the
    classic batched path with correct results (the OOM-resume
    contract of the compacted scheduler)."""
    from skdist_tpu.parallel import backend as backend_mod

    spec, fallback, shared, tasks = _toy_spec_and_tasks()
    bk = TPUBackend()
    # same round size as the fallback dispatch will use, so the
    # comparison is bitwise (round size is a program shape; different
    # shapes carry benign f32 noise)
    ref = bk.batched_map(
        fallback, tasks, shared, round_size=8,
        cache_key=("tc", "classic", "TPUBackend"),
    )

    def exploding(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED (simulated)")

    monkeypatch.setattr(backend_mod, "_run_compacted", exploding)
    with pytest.warns(UserWarning, match="falling back to the classic"):
        out = bk.batched_map_iterative(
            spec, tasks, shared, round_size=8,
            cache_key=("tc", "iter", "TPUBackend"),
        )
    np.testing.assert_array_equal(ref["W"], out["W"])
    # the refusal is booked on the dispatch that answered, and counted
    stats = bk.last_round_stats
    assert stats["refused"] == 1 and stats["retries"] == 0
    assert stats["mode"] != "compacted"


def test_iterative_no_recompile_after_warmup(tpu_backend):
    """The flags-only slice loop adds NO programs after warmup: a
    second identical run moves only hit counters, and the first run's
    AOT misses are bounded by (3 programs) x (chunk shapes)."""
    spec, _fallback, shared, tasks = _toy_spec_and_tasks()
    tpu_backend.batched_map_iterative(
        spec, tasks, shared, round_size=8,
        cache_key=("tc", "iter", "TPUBackend"),
    )
    snap1 = compile_cache.last_stats()
    tpu_backend.batched_map_iterative(
        spec, tasks, shared, round_size=8,
        cache_key=("tc", "iter", "TPUBackend"),
    )
    snap2 = compile_cache.last_stats()
    assert snap2["aot_misses"] == snap1["aot_misses"]
    assert snap2["jit_misses"] == snap1["jit_misses"]
    assert snap2["aot_hits"] > snap1["aot_hits"]
    # many slices ran in the warm pass; none of them compiled
    assert tpu_backend.last_round_stats["slices"] >= 2


# ---------------------------------------------------------------------------
# the lone round's look-ahead: its flags read one slice behind
# ---------------------------------------------------------------------------

_AHEAD_SPECS = {}


def _ahead_spec(scored):
    """The toy's L-BFGS slice kernels with their work counters and, for
    a rung, a score: ``(spec, shared, tasks)``, built once a kind so
    that the jit entries memoised under its cache key are its own."""
    if scored not in _AHEAD_SPECS:
        spec, _fallback, shared, tasks = _toy_spec_and_tasks(n_tasks=24)
        score = (lambda sh, t, c: -jnp.sum(c["w"] ** 2)) if scored else None
        _AHEAD_SPECS[scored] = (IterativeKernelSpec(
            spec.init, spec.step, spec.finalize, spec.finalize_keys,
            count_keys=("it", "nfev"), score=score), shared, tasks)
    return _AHEAD_SPECS[scored]


def _logged_run(spec, shared, tasks, chunk, monkeypatch, **kw):
    """``_run_compacted`` over executors that log what they are asked
    to run — ``init``, ``step`` (with the carry it takes and the one it
    gives), ``score`` — beside every read of a round's done flags.
    Returns ``(outputs, stats, log)``."""
    from skdist_tpu.parallel import backend as backend_mod

    log = []
    plan = LocalBackend().prepare_batched_iterative(
        spec, shared, cache_key=("tc-ahead", spec.score is not None))
    real_gather = backend_mod._flags_only_gather

    def gather(leaf):
        out = real_gather(leaf)
        if out.dtype == bool:
            log.append(("flags",))
        return out

    def logged(name, fn):
        def run(sh, sl):
            out = fn(sh, sl)
            log.append((name, sl["carry"], out) if name == "step"
                       else (name,))
            return out
        return run

    plan.init_fn = logged("init", plan.init_fn)
    plan.step_fn = logged("step", plan.step_fn)
    plan.fin_fn = logged("fin", plan.fin_fn)
    if plan.score_fn is not None:
        plan.score_fn = logged("score", plan.score_fn)
    monkeypatch.setattr(backend_mod, "_flags_only_gather", gather)
    stats = {}
    out = backend_mod._run_compacted(
        plan, spec, tasks, len(tasks["C"]), chunk, stats, **kw)
    monkeypatch.setattr(backend_mod, "_flags_only_gather", real_gather)
    return out, stats, log


def _all_done(carry):
    return bool(np.asarray(carry["done"]).all())


@pytest.mark.parametrize("n_tasks, live_rounds, lanes_fit, scored, spares", [
    # one round of 8, a device that reports no memory: ahead
    (8, None, None, False, 1),
    # three rounds run one at a time, room for two rounds' lanes: ahead
    (24, 1, 16, False, 3),
    # ... room for less than two: in step
    (24, 1, 15, False, 0),
    # two live rounds (lanes that finish together: they never compact
    # to one): in step
    (16, None, None, False, 0),
    # a rung controller: in step, its kills before the next slice
    (8, None, None, True, 0),
], ids=["lone_round", "memory_room", "memory_tight", "two_live_rounds",
        "rung"])
def test_look_ahead_engages_on_what_the_loop_observes(
        n_tasks, live_rounds, lanes_fit, scored, spares, monkeypatch):
    """The slice loop enqueues a lone round's next slice before it reads
    the flags of the slice in flight only where no rung is attached, one
    round is live and ``lanes_fit`` holds two rounds; either way the
    outputs, the counts and every decision are those of the in-step
    order (``pipeline=False``), and a spare slice over a round already
    done gives back the carry it took."""
    from skdist_tpu.parallel import RungController

    spec, shared, tasks = _ahead_spec(scored)
    tasks = {k: v[:n_tasks] for k, v in tasks.items()}
    if n_tasks == 16:
        # every lane of the second round the first round's twin
        tasks["C"] = np.full(n_tasks, 0.1, np.float32)
    runs = {}
    for side, pipeline in (("ahead", True), ("in_step", False)):
        rung = RungController(eta=2.0) if scored else None
        runs[side] = _logged_run(
            spec, shared, tasks, 8, monkeypatch, pipeline=pipeline,
            rung=rung, live_rounds=live_rounds, lanes_fit=lanes_fit) + (rung,)
    out, stats, log, rung = runs["ahead"]
    ref, ref_stats, _, ref_rung = runs["in_step"]

    # the same answers, bit for bit, and the same decisions
    for key in ref:
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(ref[key]))
    for key in ("iters", "fevals", "slices", "retired_per_slice",
                "compactions", "live_lane_slots"):
        assert stats[key] == ref_stats[key], key
    if scored:
        assert rung.killed and rung.killed == ref_rung.killed
        assert rung.history == ref_rung.history

    # the counters: spare slices are booked as dispatches of no live lane
    assert stats["spare_slices"] == spares
    assert stats["rounds"] == ref_stats["rounds"] + spares
    assert stats["lane_slots"] == ref_stats["lane_slots"] + 8 * spares
    assert ref_stats["slices_ahead"] == ref_stats["spare_slices"] == 0
    kinds = [e[0] for e in log if e[0] != "fin"]
    dispatches = [k for k in kinds if k in ("init", "step")]
    if spares:
        # every dispatch but each round's first was enqueued ahead
        n_rounds = n_tasks // 8
        assert stats["slices_ahead"] == len(dispatches) - n_rounds
        assert kinds[:3] == ["init", "step", "flags"]
    else:
        assert stats["slices_ahead"] == 0
        assert stats["rounds_per_slice"] == ref_stats["rounds_per_slice"]
        # no slice is enqueued on a carry whose flags are unread: one
        # dispatch a live round before the first read ...
        first = kinds.index("flags")
        live = n_tasks // 8 if live_rounds is None else live_rounds
        assert kinds[:first] == ["init"] * live
        if live == 1:
            # ... and a lone round's dispatches and reads alternate
            assert not any(a in ("init", "step") and b in ("init", "step")
                           for a, b in zip(kinds, kinds[1:]))
    # the spare slices ran over a round already done and gave back the
    # carry they took, leaf for leaf
    spare = [(c_in, c_out) for k, *rest in log if k == "step"
             for c_in, c_out in [rest] if _all_done(c_in)]
    assert len(spare) == spares
    for c_in, c_out in spare:
        for key in c_in:
            np.testing.assert_array_equal(np.asarray(c_in[key]),
                                          np.asarray(c_out[key]))


def test_search_look_ahead_matches_in_step(clf_data):
    """A search whose lone round reads its flags one slice behind
    answers what the same search answers with every round in step."""
    X, y = clf_data
    one_device = jax.devices()[:1]
    ahead_bk = TPUBackend(devices=one_device)
    in_step_bk = TPUBackend(devices=one_device, sync_rounds=True)
    ahead = _skewed_grid_search(ahead_bk, X, y, partitions=1)
    in_step = _skewed_grid_search(in_step_bk, X, y, partitions=1)
    stats, ref_stats = ahead_bk.last_round_stats, in_step_bk.last_round_stats
    assert stats["mode"] == ref_stats["mode"] == "compacted"
    assert stats["slices_ahead"] > 0 and stats["spare_slices"] == 1
    assert ref_stats["slices_ahead"] == ref_stats["spare_slices"] == 0
    assert (stats["iters"], stats["fevals"]) == (
        ref_stats["iters"], ref_stats["fevals"])
    for key, value in in_step.cv_results_.items():
        if key.endswith("_time"):
            continue
        if isinstance(value, np.ndarray) and value.dtype.kind == "f":
            np.testing.assert_array_equal(ahead.cv_results_[key], value)
        else:
            assert list(ahead.cv_results_[key]) == list(value), key


# ---------------------------------------------------------------------------
# scheduler integration: search path
# ---------------------------------------------------------------------------

def _skewed_grid_search(backend, X, y, **kwargs):
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression

    grid = {
        "C": [0.01, 0.1, 1.0, 10.0],
        "tol": [1e-2, 1e-5],
    }  # 8 candidates x 3 folds = 24 tasks >= the compaction floor
    return DistGridSearchCV(
        LogisticRegression(max_iter=40, engine="xla"), grid,
        backend=backend, cv=3, scoring="accuracy", **kwargs,
    ).fit(X, y)


def test_search_compacted_matches_classic_and_generic(clf_data, monkeypatch):
    from sklearn.metrics import accuracy_score, make_scorer

    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression

    X, y = clf_data
    bk = TPUBackend()
    compacted = _skewed_grid_search(bk, X, y)
    assert bk.last_round_stats["mode"] == "compacted"
    monkeypatch.setenv("SKDIST_COMPACTION", "0")
    bk2 = TPUBackend()
    classic = _skewed_grid_search(bk2, X, y)
    assert bk2.last_round_stats["mode"] in ("pipelined", "synchronous")
    monkeypatch.delenv("SKDIST_COMPACTION")
    generic = DistGridSearchCV(
        LogisticRegression(max_iter=40, engine="xla"),
        {"C": [0.01, 0.1, 1.0, 10.0], "tol": [1e-2, 1e-5]}, cv=3,
        scoring=make_scorer(accuracy_score),
    ).fit(X, y)
    np.testing.assert_allclose(
        compacted.cv_results_["mean_test_score"],
        classic.cv_results_["mean_test_score"],
        atol=1e-5,
    )
    np.testing.assert_allclose(
        compacted.cv_results_["mean_test_score"],
        generic.cv_results_["mean_test_score"],
        atol=1e-5,
    )
    assert compacted.best_params_ == classic.best_params_


def test_search_one_round_matches_eight():
    """A search whose shared matrix outweighs its lanes runs as ONE
    round holding every fit (ISSUE 27) and answers what the same search
    answers in eight rounds; the stats say which rule sized the round."""
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression

    rng = np.random.RandomState(3)
    # wide enough that 24 lanes' own bytes (their row-sized values at
    # the program's fullest point) stay under the matrix's
    X = rng.normal(size=(4000, 320)).astype(np.float32)
    y = (X @ rng.normal(size=320) + rng.normal(size=4000) > 0).astype(int)

    def search(backend, partitions):
        return DistGridSearchCV(
            LogisticRegression(max_iter=40, engine="xla"),
            {"C": [float(c) for c in np.logspace(-3, 2, 8)]},
            backend=backend, cv=3, scoring="neg_log_loss",
            partitions=partitions,
        ).fit(X, y)

    one_device = jax.devices()[:1]
    bk = TPUBackend(devices=one_device)
    one = search(bk, "auto")
    stats = bk.last_round_stats
    assert stats["mode"] == "compacted"
    assert (stats["chunk"], stats["chunk_basis"]) == (24, "all_tasks")
    assert stats["lanes_fit"] is None  # the CPU reports no memory
    # the lone round reads its flags one slice behind: every dispatch
    # but the first is enqueued ahead, and the last ran over a round
    # already done
    assert stats["rounds"] == stats["slices"] + 1
    assert stats["slices_ahead"] == stats["rounds"] - 1
    assert stats["spare_slices"] == 1
    assert stats["compactions"] == 0
    assert stats["lane_slots"] == stats["chunk"] * stats["rounds"]
    assert 0 < stats["live_lane_slots"] < stats["lane_slots"]

    bk8 = TPUBackend(devices=one_device)
    eight = search(bk8, 8)
    stats8 = bk8.last_round_stats
    assert (stats8["chunk"], stats8["chunk_basis"]) == (3, "round_size")
    assert stats8["lane_slots"] == 3 * stats8["rounds"]
    for key in ("mean_test_score", "split0_test_score",
                "split2_test_score"):
        np.testing.assert_allclose(
            one.cv_results_[key], eight.cv_results_[key], atol=1e-5)
    assert one.best_params_ == eight.best_params_
    # the lanes did the same work in either shape
    assert sum(stats["iters"]) == pytest.approx(sum(stats8["iters"]), rel=0.05)


def test_cost_permutation_round_trip_pins_row_order(clf_data):
    """Cost-ordered round packing is a scheduler detail: cv_results_
    rows stay in candidate-enumeration order with their own values
    (the permutation is undone before _format_results)."""
    from sklearn.model_selection import ParameterGrid

    X, y = clf_data
    grid = {"C": [10.0, 0.01, 1.0, 0.1], "tol": [1e-5, 1e-2]}
    bk = TPUBackend()
    gs = _skewed_grid_search(bk, X, y)
    # candidate order in cv_results_ == ParameterGrid enumeration order
    expected = list(ParameterGrid(
        {"C": [0.01, 0.1, 1.0, 10.0], "tol": [1e-2, 1e-5]}
    ))
    assert gs.cv_results_["params"] == expected
    np.testing.assert_array_equal(
        np.asarray([p["C"] for p in gs.cv_results_["params"]]),
        np.asarray(gs.cv_results_["param_C"].compressed(), dtype=float),
    )


def test_search_oom_mid_compaction_parity(clf_data, monkeypatch):
    """Forced _RoundsExhausted during the compacted search: results
    must still match the classic path (fallback kernel takes over)."""
    from skdist_tpu.parallel import backend as backend_mod

    X, y = clf_data
    monkeypatch.setenv("SKDIST_COMPACTION", "0")
    classic = _skewed_grid_search(TPUBackend(), X, y)
    monkeypatch.delenv("SKDIST_COMPACTION")

    real = backend_mod._run_compacted
    calls = []

    def flaky(*a, **k):
        if not calls:
            calls.append(1)
            raise RuntimeError("RESOURCE_EXHAUSTED (simulated)")
        return real(*a, **k)

    monkeypatch.setattr(backend_mod, "_run_compacted", flaky)
    with pytest.warns(UserWarning, match="falling back to the classic"):
        compacted = _skewed_grid_search(TPUBackend(), X, y)
    np.testing.assert_allclose(
        compacted.cv_results_["mean_test_score"],
        classic.cv_results_["mean_test_score"],
        atol=1e-6,
    )


def test_small_grids_stay_on_classic_path(clf_data):
    """Below the task floor the classic fused kernel still runs (its
    bitwise behaviour is pinned by the existing parity tests)."""
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression

    X, y = clf_data
    bk = TPUBackend()
    DistGridSearchCV(
        LogisticRegression(max_iter=40, engine="xla"),
        {"C": [0.1, 1.0]}, backend=bk, cv=3, scoring="accuracy",
    ).fit(X, y)
    assert bk.last_round_stats["mode"] in ("pipelined", "synchronous")


def test_gate_respects_env_and_sizes(tpu_backend):
    from skdist_tpu.models import LogisticRegression, Ridge

    assert iterative_fit_supported(
        tpu_backend, LogisticRegression, 64, 100
    ) is not None
    # too few tasks / no max_iter / unsupported family
    assert iterative_fit_supported(
        tpu_backend, LogisticRegression, 8, 100
    ) is None
    assert iterative_fit_supported(
        tpu_backend, LogisticRegression, 64, None
    ) is None
    assert iterative_fit_supported(tpu_backend, Ridge, 64, 100) is None
    os.environ["SKDIST_COMPACTION"] = "0"
    try:
        assert iterative_fit_supported(
            tpu_backend, LogisticRegression, 64, 100
        ) is None
    finally:
        del os.environ["SKDIST_COMPACTION"]


# ---------------------------------------------------------------------------
# OvR / OvO through the same entry point
# ---------------------------------------------------------------------------

def test_ovr_ovo_compacted_parity():
    from skdist_tpu.distribute.multiclass import (
        DistOneVsOneClassifier,
        DistOneVsRestClassifier,
    )
    from skdist_tpu.models import LogisticRegression

    rng = np.random.RandomState(1)
    # OvR: 26 class columns >= the 24-task compaction floor
    n, d, k = 260, 8, 26
    X = rng.normal(size=(n, d)).astype(np.float32)
    W = rng.normal(size=(d, k)).astype(np.float32)
    y = np.argmax(X @ W + rng.normal(size=(n, k)), axis=1)
    est = LogisticRegression(max_iter=40, tol=1e-4, engine="xla")

    bk = TPUBackend()
    ovr_c = DistOneVsRestClassifier(est, backend=bk).fit(X, y)
    assert bk.last_round_stats["mode"] == "compacted"
    os.environ["SKDIST_COMPACTION"] = "0"
    try:
        ovr_k = DistOneVsRestClassifier(est, backend=TPUBackend()).fit(X, y)
    finally:
        del os.environ["SKDIST_COMPACTION"]
    assert (ovr_c.predict(X) == ovr_k.predict(X)).all()
    np.testing.assert_allclose(
        ovr_c.predict_proba(X), ovr_k.predict_proba(X), atol=1e-4
    )

    # OvO: 9 classes -> 36 pairs >= the floor (a host predict loop over
    # hundreds of pairs would dominate the test for no extra coverage)
    k2 = 9
    y2 = np.argmax(X @ W[:, :k2] + rng.normal(size=(n, k2)), axis=1)
    bk2 = TPUBackend()
    ovo_c = DistOneVsOneClassifier(est, backend=bk2).fit(X, y2)
    assert bk2.last_round_stats["mode"] == "compacted"
    os.environ["SKDIST_COMPACTION"] = "0"
    try:
        ovo_k = DistOneVsOneClassifier(est, backend=TPUBackend()).fit(X, y2)
    finally:
        del os.environ["SKDIST_COMPACTION"]
    assert (ovo_c.predict(X) == ovo_k.predict(X)).all()
