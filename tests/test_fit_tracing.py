"""
The measurement inside a search fit:

- the L-BFGS carry counts the loss evaluations each lane asked for
  (``nfev``) — against an independent plain-Python count, sliced ==
  unsliced, per lane under ``vmap``;
- the compacted round loop brings the per-task counts out
  (``iters`` / ``fevals``) in the caller's task order and counts its
  lane slots;
- one ``DistGridSearchCV.fit`` is one span tree from ``search_fit``
  down to the round loop, and leaves nothing behind with tracing off;
- what JAX reports of a compile (trace, lowering, backend compile,
  persistent-cache hit or miss) lands in the fit's tree and in the
  registry, the export tier's reads and writes beside it;
- the solver's phases are named in the compiled program.
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from skdist_tpu.distribute.search import DistGridSearchCV
from skdist_tpu.models import LogisticRegression
from skdist_tpu.models.solvers import (
    LBFGS_CARRY_KEYS,
    lbfgs_carry_init,
    lbfgs_minimize,
    lbfgs_resume,
)
from skdist_tpu.obs import trace as obs_trace
from skdist_tpu.parallel import IterativeKernelSpec, LocalBackend, TPUBackend


# ---------------------------------------------------------------------------
# (a) nfev in the carry
# ---------------------------------------------------------------------------

def _py_lbfgs(f, grad, w0, max_iter, tol, m=10, max_ls=20, eps=1e-12):
    """Plain-Python L-BFGS with the solver's rules (two-loop direction,
    unit-normalised raw directions, Armijo halving from t=1, curvature
    check); returns ``(n_iter, n_evaluations)``."""
    w, fval, g, nfev = w0.copy(), f(w0), grad(w0), 1
    S, Y, rho = [], [], []
    it = 0
    if np.max(np.abs(g)) <= tol:
        return it, nfev
    while it < max_iter:
        q, alphas = g.copy(), []
        for s, y, r in zip(reversed(S), reversed(Y), reversed(rho)):
            alphas.append(r * s.dot(q))
            q = q - alphas[-1] * y
        if S:
            q = q * (S[-1].dot(Y[-1]) / (Y[-1].dot(Y[-1]) + eps))
        for s, y, r, a in zip(S, Y, rho, reversed(alphas)):
            q = q + s * (a - r * y.dot(q))
        d = -q
        descent = g.dot(d) < 0
        if not descent:
            d = -g
        if not descent or not S:
            d = d / (np.linalg.norm(d) + eps)
        gd = g.dot(d)
        t, halved = 1.0, 0
        f_new = f(w + t * d)
        nfev += 1
        while not f_new <= fval + 1e-4 * t * gd and halved < max_ls:
            t *= 0.5
            f_new = f(w + t * d)
            nfev += 1
            halved += 1
        ok = f_new <= fval + 1e-4 * t * gd
        w_new = w + t * d
        f2, g_new = f(w_new), grad(w_new)
        nfev += 1
        s, y = w_new - w, g_new - g
        if s.dot(y) > 1e-10:
            S, Y = (S + [s])[-m:], (Y + [y])[-m:]
            rho = (rho + [1.0 / (s.dot(y) + eps)])[-m:]
        w, fval, g = w_new, f2, g_new
        it += 1
        if np.max(np.abs(g)) <= tol or not ok:
            break
    return it, nfev


def _logreg(seed, reg=1e-4, n=48, d=7, ray=False):
    """A small logistic objective as the solver's ``loss`` (with
    ``ray=True`` offering ``loss.ray``, the solver's other branch: the
    same objective searched from two products a direction) and as
    float64 ``f`` / ``grad`` for the plain-Python count."""
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, d))
    y = (rng.rand(n) > 0.5).astype(np.float64)
    Xj, yj = jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32)

    def rows(z):
        return jnp.sum(jax.nn.softplus(z) - yj * z)

    def loss(w, reg=reg):
        return rows(Xj @ w) + reg * jnp.dot(w, w)

    def loss_ray(w, d, reg=reg):
        z0, dz = Xj @ w, Xj @ d

        def along(t):
            return rows(z0 + t * dz) + reg * jnp.dot(w + t * d, w + t * d)

        def value_and_grad_at(t):
            f, r = jax.value_and_grad(rows)(z0 + t * dz)
            w_new = w + t * d
            return (f + reg * jnp.dot(w_new, w_new),
                    Xj.T @ r + 2 * reg * w_new)

        return along, value_and_grad_at

    if ray:
        loss.ray = loss_ray

    def f(w):
        z = X @ w
        return np.sum(np.logaddexp(0, z) - y * z) + reg * w.dot(w)

    def grad(w):
        z = X @ w
        return X.T @ (1.0 / (1.0 + np.exp(-z)) - y) + 2 * reg * w

    return loss, f, grad, d


def _solve(loss, w0, max_iter, tol, n_slice=None):
    """The carry after a whole solve: one full-length resume, or
    chained resumes of ``n_slice`` iterations."""
    carry = jax.jit(
        lambda w0: lbfgs_carry_init(loss, w0, max_iter, tol)
    )(w0)
    step = jax.jit(lambda c: lbfgs_resume(
        loss, c, n_slice or max_iter, max_iter, tol))
    for _ in range(200):
        if bool(carry["done"]):
            break
        carry = step(carry)
    assert bool(carry["done"])
    return carry


def test_nfev_is_a_carry_leaf_and_minimize_keeps_its_return():
    assert "nfev" in LBFGS_CARRY_KEYS
    loss, _f, _g, d = _logreg(0)
    out = lbfgs_minimize(loss, jnp.zeros(d, jnp.float32), 40, 1e-3)
    assert len(out) == 2  # still (w, n_iter)


@pytest.mark.parametrize("seed,ray", [
    (0, False), (2, False), (5, False), (0, True), (4, True), (5, True)])
def test_nfev_matches_an_independent_count(seed, ray):
    """One meaning on both branches of the solver: the evaluations the
    line search asked for, whether a trial step is a product (a plain
    function) or a pass over the ray's logits. (Seeds whose stop test
    does not sit on a float32 rounding: at seed 2 the ray's gradient
    passes ``tol`` one iteration later than the float64 count's.)"""
    max_iter, tol = 40, 1e-3
    loss, f, grad, d = _logreg(seed, ray=ray)
    carry = _solve(loss, jnp.zeros(d, jnp.float32), max_iter, tol)
    it, nfev = _py_lbfgs(f, grad, np.zeros(d), max_iter, tol)
    assert (int(carry["it"]), int(carry["nfev"])) == (it, nfev)
    assert nfev >= 2 * it + 1


def test_nfev_counts_one_evaluation_before_any_iteration():
    loss, _f, _g, d = _logreg(1)
    carry = lbfgs_carry_init(loss, jnp.zeros(d, jnp.float32), 40, 1e-3)
    assert int(carry["nfev"]) == 1 and int(carry["it"]) == 0


@pytest.mark.parametrize("ray", [False, True])
@pytest.mark.parametrize("seed", [0, 3])
def test_nfev_sliced_equals_unsliced_bitwise(seed, ray):
    """Slices of 3 against the unsliced solve: ``w``, ``it`` and
    ``nfev`` bit for bit (the counter rides the same carry)."""
    max_iter, tol = 33, 1e-5
    loss, _f, _g, d = _logreg(seed, reg=0.05, ray=ray)
    w0 = jnp.zeros(d, jnp.float32)
    whole = _solve(loss, w0, max_iter, tol)
    sliced = _solve(loss, w0, max_iter, tol, n_slice=3)
    for key in ("w", "it", "nfev"):
        np.testing.assert_array_equal(
            np.asarray(whole[key]), np.asarray(sliced[key])
        )
    assert int(whole["nfev"]) >= 2 * int(whole["it"]) + 1


@pytest.mark.parametrize("ray", [False, True])
def test_nfev_is_per_lane_under_vmap(ray):
    """A strongly regularised lane converges in fewer evaluations than
    a weakly regularised one in the same vmapped program, and each
    lane reads what it reads beside a copy of itself (the same program
    at the same batch width: only the peer differs)."""
    max_iter, tol = 60, 1e-4
    loss, _f, _g, d = _logreg(4, ray=ray)
    w0 = jnp.zeros(d, jnp.float32)
    Cs = jnp.asarray([1e-3, 1e3], jnp.float32)

    def fit(C):
        def lane_loss(w):
            return loss(w, reg=0.5 / C)

        if ray:
            lane_loss.ray = lambda w, d: loss.ray(w, d, reg=0.5 / C)

        carry = lbfgs_carry_init(lane_loss, w0, max_iter, tol)
        carry = lbfgs_resume(lane_loss, carry, max_iter, max_iter, tol)
        return carry["it"], carry["nfev"]

    solve = jax.jit(jax.vmap(fit))
    it, nfev = solve(Cs)
    assert int(nfev[0]) < int(nfev[1])
    for lane, C in enumerate(Cs):
        it_2, nfev_2 = solve(jnp.stack([C, C]))
        assert int(it_2[0]) == int(it_2[1]) == int(it[lane])
        assert int(nfev_2[0]) == int(nfev_2[1]) == int(nfev[lane])
    assert (np.asarray(nfev) >= 2 * np.asarray(it) + 1).all()


# ---------------------------------------------------------------------------
# (b) the compacted loop brings the counts out
# ---------------------------------------------------------------------------

def _search_data(seed=0, n=400, d=12):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] + 0.5 * rng.normal(size=n) > 0).astype(np.int64)
    return X, y


def _small_search(backend, Cs, cv=4, max_iter=50):
    # eight rounds, stated: these tests are about what a fit books, not
    # about how the backend sizes a round by itself
    return DistGridSearchCV(
        LogisticRegression(max_iter=max_iter, engine="xla"), {"C": Cs},
        backend=backend, cv=cv, scoring="neg_log_loss", partitions=8,
    )


@pytest.fixture(scope="module")
def one_device_backend():
    return TPUBackend(devices=jax.devices()[:1])


def test_compacted_counts_in_caller_order(one_device_backend):
    """``iters`` / ``fevals`` have one entry per (candidate, fold) in
    candidate-major order although the rounds were packed by cost, and
    equal what the unsliced solve of the same problem reads."""
    from sklearn.model_selection import StratifiedKFold

    from skdist_tpu.models.linear import _freeze, maybe_exact_matmuls

    X, y = _search_data()
    # descending C: the cost order (ascending C) reverses the task axis
    Cs = [float(c) for c in np.logspace(3, -3, 8)]
    cv, max_iter = 4, 50
    backend = one_device_backend
    _small_search(backend, Cs, cv, max_iter).fit(X, y)
    stats = backend.last_round_stats
    assert stats["mode"] == "compacted"
    n_tasks = len(Cs) * cv
    assert len(stats["iters"]) == len(stats["fevals"]) == n_tasks
    assert 0 < stats["live_lane_slots"] <= stats["lane_slots"]
    assert stats["lane_slots"] == stats["chunk"] * stats["rounds"]

    est = LogisticRegression(max_iter=max_iter, engine="xla")
    data, meta = est._prep_fit_data(X, y, None)
    static = _freeze(est._static_config(meta))
    problem = LogisticRegression._build_fit_problem(meta, static)
    masks = np.zeros((cv, len(y)), np.float32)
    for s, (train, _test) in enumerate(
            StratifiedKFold(cv).split(X, y)):
        masks[s, train] = 1.0

    def whole(C, mask, X):
        hyper = {"C": C, "tol": jnp.float32(est.tol)}
        loss, w0, _ = problem(X, data["y"], data["sw"] * mask, hyper)
        carry = lbfgs_carry_init(loss, w0, max_iter, hyper["tol"])
        carry = lbfgs_resume(loss, carry, max_iter, max_iter,
                             hyper["tol"])
        return carry["it"], carry["nfev"]

    # candidate-major, split fastest; batches as wide as a round,
    # since a CPU matmul's rounding may follow the batch width
    task_C = np.repeat(np.asarray(Cs, np.float32), cv)
    task_mask = np.tile(masks, (len(Cs), 1))
    # X an argument, as the search's programs take it: closed over, the
    # compiler folds the products' operand and rounds them otherwise
    solve = jax.jit(jax.vmap(
        maybe_exact_matmuls(LogisticRegression, whole),
        in_axes=(0, 0, None)))
    chunk = stats["chunk"]
    assert n_tasks % chunk == 0
    want_it, want_nfev = [], []
    for lo in range(0, n_tasks, chunk):
        it, nfev = solve(task_C[lo:lo + chunk], task_mask[lo:lo + chunk],
                         data["X"])
        want_it += [int(v) for v in it]
        want_nfev += [int(v) for v in nfev]
    assert stats["iters"] == want_it
    assert stats["fevals"] == want_nfev
    # ... which the dispatch order would not have passed for
    order = np.lexsort((np.tile(np.arange(cv), len(Cs)), task_C))
    assert [want_nfev[i] for i in order] != want_nfev


def test_spec_without_count_keys_leaves_counts_none():
    def init(shared, task):
        left = task["n"].astype(np.int32)
        return {"left": left, "done": left <= 0}

    def step(shared, task, carry):
        left = carry["left"] - 1
        return {"left": left, "done": left <= 0}

    spec = IterativeKernelSpec(
        init, step, lambda sh, t, c: {"left": c["left"]}, ("left",))
    assert spec.count_keys == ()
    bk = LocalBackend()
    bk.batched_map_iterative(
        spec, {"n": np.arange(20, dtype=np.float32) % 3}, {}, round_size=8)
    stats = bk.last_round_stats
    assert stats["mode"] == "compacted"
    for key in ("iters", "fevals", "lane_slots", "live_lane_slots"):
        assert stats[key] is None


def test_count_keys_ride_the_toy_carry():
    """Any spec may name its counters: a countdown whose carry counts
    its own steps books them per task, and padding lanes and lanes that
    ride on after finishing show in the slot counts."""
    def init(shared, task):
        left = task["n"].astype(np.int32)
        return {"left": left, "steps": jnp.zeros_like(left),
                "evals": jnp.ones_like(left), "done": left <= 0}

    def step(shared, task, carry):
        live = ~carry["done"]
        left = carry["left"] - live
        return {"left": left, "steps": carry["steps"] + live,
                "evals": carry["evals"] + 2 * live, "done": left <= 0}

    spec = IterativeKernelSpec(
        init, step, lambda sh, t, c: {"left": c["left"]}, ("left",),
        count_keys=("steps", "evals"))
    n = np.arange(21, dtype=np.float32) % 4
    bk = LocalBackend()
    bk.batched_map_iterative(spec, {"n": n}, {}, round_size=8)
    stats = bk.last_round_stats
    assert stats["iters"] == [int(v) for v in n]
    assert stats["fevals"] == [1 + 2 * int(v) for v in n]
    assert stats["lane_slots"] == 8 * stats["rounds"]
    # every lane is live in its init round, then once per step it takes
    assert stats["live_lane_slots"] == int(len(n) + n.sum())
    assert stats["live_lane_slots"] < stats["lane_slots"]


# ---------------------------------------------------------------------------
# (c) one span tree per fit
# ---------------------------------------------------------------------------

CHILDREN = ("cv_split", "prepare_data", "place_shared", "round_loop",
            "finalize", "format_results", "refit")


@pytest.fixture
def tracing():
    obs_trace.clear()
    obs_trace.set_enabled(True)
    yield
    obs_trace.set_enabled(False)
    obs_trace.clear()


def test_one_span_tree_per_fit(tracing, one_device_backend):
    X, y = _search_data(1)
    Cs = [float(c) for c in np.logspace(-3, 3, 8)]
    _small_search(one_device_backend, Cs).fit(X, y)
    assert obs_trace.dropped() == 0
    spans = [e for e in obs_trace.events() if e[1] == "X"]
    roots = [e for e in spans if e[0] == "search_fit"]
    assert len(roots) == 1
    root = roots[0]
    assert root[5]["n_tasks"] == 32 and root[5]["n_splits"] == 4
    trace_id, root_id = root[5]["trace_id"], root[5]["span_id"]
    assert all(e[5]["trace_id"] == trace_id for e in obs_trace.events())

    by_id = {e[5]["span_id"]: e for e in spans}
    children = sorted(
        (e for e in spans if e[5]["parent_id"] == root_id),
        key=lambda e: e[2])
    names = [e[0] for e in children]
    assert set(names) - {"compile"} == set(CHILDREN)
    order = [n for n in names if n != "compile"]
    first = [order.index(n) for n in CHILDREN]
    assert first == sorted(first), order
    # siblings do not overlap, and together they fit inside the root
    for a, b in zip(children, children[1:]):
        assert a[2] + a[3] <= b[2] + 1e-9
    assert sum(e[3] for e in children) <= root[3]
    assert all(root[2] <= e[2] and e[2] + e[3] <= root[2] + root[3] + 1e-9
               for e in children)

    def parent_name(e):
        return by_id[e[5]["parent_id"]][0]

    for e in spans:
        if e[0] == "flags_wait":
            assert parent_name(e) == "round_loop"
        if e[0] == "round_gather":
            assert parent_name(e) == "finalize"
        if e[0] == "round_dispatch":
            assert parent_name(e) in ("round_loop", "finalize")
    for name in ("flags_wait", "round_gather", "round_dispatch"):
        assert any(e[0] == name for e in spans)
    # the search places X by itself, once a fit; the dispatch's span
    # then counts the whole tree, X by reference
    placed = [e for e in children if e[0] == "place_shared"]
    assert len(placed) == 2 and placed[0][5]["bytes"] == X.nbytes
    assert placed[1][5]["bytes"] > X.nbytes


def test_two_fits_are_two_trees(tracing, one_device_backend):
    X, y = _search_data(2)
    Cs = [float(c) for c in np.logspace(-2, 2, 8)]
    for _ in range(2):
        _small_search(one_device_backend, Cs).fit(X, y)
    roots = [e for e in obs_trace.events() if e[0] == "search_fit"]
    assert len(roots) == 2
    assert roots[0][5]["trace_id"] != roots[1][5]["trace_id"]
    assert obs_trace.current_context() is None


def test_untraced_fit_leaves_the_ring_empty(one_device_backend):
    obs_trace.set_enabled(False)
    obs_trace.clear()
    X, y = _search_data(3)
    Cs = [float(c) for c in np.logspace(-2, 2, 8)]
    _small_search(one_device_backend, Cs).fit(X, y)
    assert one_device_backend.last_round_stats["mode"] == "compacted"
    assert obs_trace.events() == []
    assert obs_trace.span("search_fit") is obs_trace._NOOP
    assert obs_trace.current_context() is None


# ---------------------------------------------------------------------------
# (c2) the compile path reports itself
# ---------------------------------------------------------------------------

#: what JAX announces of a compile, as the program's listeners record it
ANNOUNCED = ("jax_trace", "jax_lower", "xla_compile")
#: ... and every span that names a part of a first fit's extra seconds
COMPILE_SPANS = ANNOUNCED + ("compile", "export_read", "export_write",
                             "lane_footprint")


@pytest.fixture(scope="module")
def fresh_cache_dir(tmp_path_factory):
    """The persistent compile cache (XLA's entries and the export
    tier) re-pointed at an empty directory for this module's compile
    passes, and put back."""
    from jax.experimental.compilation_cache import compilation_cache

    from skdist_tpu.parallel import compile_cache

    fresh = str(tmp_path_factory.mktemp("compile_cache"))
    was = compile_cache.enable_disk_cache()
    jax.config.update("jax_compilation_cache_dir", fresh)
    compilation_cache.reset_cache()
    compile_cache._DISK_DIR = fresh
    yield fresh
    compile_cache._DISK_DIR = was
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()
    compile_cache.clear_memos()


@pytest.fixture(scope="module")
def compile_passes(fresh_cache_dir):
    """Four fits of one search on one device, and what each left in the
    ring and moved in ``snapshot()``: ``cold`` (memos cleared, the cache
    directory empty), ``second`` (the same process again), ``warm_disk``
    (memos cleared, the directory kept: what a second process finds)
    and ``untraced`` (memos cleared again, tracing off)."""
    from skdist_tpu.parallel import compile_cache

    X, y = _search_data(4)
    Cs = [float(c) for c in np.logspace(-3, 3, 8)]
    backend = TPUBackend(devices=jax.devices()[:1])
    passes = {}
    for name in ("cold", "second", "warm_disk", "untraced"):
        if name != "second":
            compile_cache.clear_memos()
        obs_trace.clear()
        obs_trace.set_enabled(name != "untraced")
        before = compile_cache.snapshot()
        try:
            _small_search(backend, Cs).fit(X, y)
        finally:
            obs_trace.set_enabled(False)
        after = compile_cache.snapshot()
        passes[name] = {
            "events": obs_trace.events(),
            "moved": {k: after[k] - before[k] for k in after
                      if k != "disk_cache_dir"},
            "mode": backend.last_round_stats["mode"],
        }
        obs_trace.clear()
    return passes


def _spans(events, *names):
    return [e for e in events if e[1] == "X" and e[0] in names]


def _ancestors(events, e):
    """Names of the spans that enclose ``e`` by their ids."""
    by_id = {x[5]["span_id"]: x for x in events
             if x[1] == "X" and x[5] and "span_id" in x[5]}
    names = []
    while e is not None:
        e = by_id.get(e[5].get("parent_id"))
        if e is not None:
            names.append(e[0])
    return names


def test_cold_fit_holds_what_jax_reports_in_its_tree(compile_passes):
    events = compile_passes["cold"]["events"]
    assert compile_passes["cold"]["mode"] == "compacted"
    (root,) = _spans(events, "search_fit")
    compiles = _spans(events, "xla_compile")
    assert compiles
    for e in compiles + _spans(events, "jax_trace", "jax_lower"):
        assert e[5]["trace_id"] == root[5]["trace_id"]
        assert isinstance(e[5]["fun"], str) and e[5]["fun"]
        assert e[3] >= 0 and "search_fit" in _ancestors(events, e)
    assert all(e[5]["cache"] == "miss" for e in compiles)
    assert _spans(events, "jax_trace") and _spans(events, "jax_lower")


def test_cold_fit_compiles_outside_every_compile_span(compile_passes):
    """The ``fit`` kernel is a ``jax.jit`` object built under
    ``kernel_memo``: its trace, lowering and compile happen at its
    first call, under ``refit``, where no ``compile`` span is open."""
    events = compile_passes["cold"]["events"]
    outside = [e for e in _spans(events, "xla_compile")
               if "compile" not in _ancestors(events, e)]
    assert outside
    assert any("refit" in _ancestors(events, e) for e in outside)
    assert any("kernel" in e[5]["fun"] for e in outside)
    inside = [e for e in _spans(events, "xla_compile")
              if "compile" in _ancestors(events, e)]
    assert inside  # the round loop's programs, under the aot tier


def test_cold_fit_writes_its_exports_under_the_aot_compile_span(
        compile_passes, fresh_cache_dir):
    import os

    events = compile_passes["cold"]["events"]
    writes = _spans(events, "export_write")
    assert writes and not _spans(events, "export_read")
    by_id = {e[5]["span_id"]: e for e in _spans(events, "compile")}
    for w in writes:
        parent = by_id[w[5]["parent_id"]]
        assert parent[5]["tier"] == "aot"
        assert parent[5]["export"] == "write"
        assert w[5]["bytes"] > 0
        assert parent[2] <= w[2] and w[3] <= parent[3]
    aot = [e for e in by_id.values() if e[5].get("tier") == "aot"]
    assert len(aot) == len(writes)
    assert all("export" not in e[5] for e in by_id.values()
               if e[5].get("tier") != "aot")
    files = os.listdir(os.path.join(fresh_cache_dir, "aot_exports"))
    assert sorted(w[5]["bytes"] for w in writes) == sorted(
        os.path.getsize(os.path.join(fresh_cache_dir, "aot_exports", f))
        for f in files)
    moved = compile_passes["cold"]["moved"]
    assert moved["aot_export_writes"] == len(writes)
    assert moved["backend_compiles"] == len(_spans(events, "xla_compile"))
    assert moved["xla_cache_misses"] == moved["backend_compiles"]


def test_cold_fit_spans_the_lane_footprint_once(compile_passes):
    events = compile_passes["cold"]["events"]
    (probe,) = _spans(events, "lane_footprint")
    assert "search_fit" in _ancestors(events, probe)
    # the abstract trace reports itself inside it
    assert any(probe[2] <= e[2] and e[2] + e[3] <= probe[2] + probe[3] + 1e-3
               for e in _spans(events, "jax_trace"))


@pytest.mark.parametrize("name", COMPILE_SPANS)
def test_second_fit_of_a_process_holds_no_compile_span(
        compile_passes, name):
    events = compile_passes["second"]["events"]
    assert _spans(events, "search_fit") and _spans(events, "round_dispatch")
    assert not _spans(events, name)
    moved = compile_passes["second"]["moved"]
    assert moved["backend_compiles"] == 0


def test_warm_disk_pass_reads_exports_and_hits_xlas_cache(compile_passes):
    events = compile_passes["warm_disk"]["events"]
    reads = _spans(events, "export_read")
    assert reads and not _spans(events, "export_write")
    assert all(r[5]["bytes"] > 0 for r in reads)
    aot = [e for e in _spans(events, "compile")
           if e[5].get("tier") == "aot"]
    assert len(aot) == len(reads)
    assert all(e[5]["export"] == "hit" for e in aot)
    compiles = _spans(events, "xla_compile")
    assert compiles and all(e[5]["cache"] == "hit" for e in compiles)
    # the re-lowering of the deserialized program reports itself
    assert any("compile" in _ancestors(events, e)
               for e in _spans(events, "jax_lower"))
    moved = compile_passes["warm_disk"]["moved"]
    assert moved["aot_export_hits"] == len(reads)
    assert moved["aot_export_writes"] == 0
    assert moved["backend_compiles"] == len(compiles)
    assert moved["xla_cache_misses"] == 0


def test_untraced_compiles_bill_the_registry_and_leave_the_ring_empty(
        compile_passes):
    untraced = compile_passes["untraced"]
    assert untraced["events"] == []
    moved = untraced["moved"]
    assert moved["backend_compiles"] >= 2
    assert moved["xla_cache_misses"] == 0  # the directory is warm
    # what was there keeps its meaning beside the new keys
    assert moved["aot_misses"] >= 1 and moved["lower_time_s"] > 0


def test_listeners_are_registered_once_a_process():
    from jax._src import monitoring

    from skdist_tpu.parallel import compile_cache

    compile_cache.enable_disk_cache()
    compile_cache._listen()
    compile_cache.snapshot()
    assert monitoring.get_event_listeners().count(
        compile_cache._on_jax_event) == 1
    assert monitoring.get_event_time_span_listeners().count(
        compile_cache._on_jax_time_span) == 1
    before = compile_cache.snapshot()["backend_compiles"]
    jax.jit(lambda x: x * 3 + 1)(np.float32(2)).block_until_ready()
    assert compile_cache.snapshot()["backend_compiles"] == before + 1


def test_a_jax_without_the_hooks_warns_and_builds(monkeypatch):
    """An observability hook never stops a backend from being built."""
    from skdist_tpu.parallel import compile_cache

    monkeypatch.setattr(compile_cache, "_LISTENING", False)
    monkeypatch.delattr(jax.monitoring, "register_event_listener")
    with pytest.warns(RuntimeWarning, match="announces no compile events"):
        compile_cache.snapshot()
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # said once, not at every call
        compile_cache.snapshot()
        TPUBackend(devices=jax.devices()[:1])


@pytest.mark.parametrize("left", [None, "miss"])
def test_compile_cache_off_reads_off(tracing, left):
    """A compile that does not ask XLA's persistent cache says so —
    also after a compile that raised left its ``miss`` on the thread:
    the lowering before the next compile drops it."""
    from jax.experimental.compilation_cache import compilation_cache

    from skdist_tpu.parallel import compile_cache

    compile_cache._COMPILING.cache = left
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        jax.jit(lambda x: x * 5 - 1)(np.float32(2)).block_until_ready()
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()
    (compiled,) = _spans(obs_trace.events(), "xla_compile")
    assert compiled[5]["cache"] == "off"
    assert "trace_id" not in compiled[5]  # no fit's context was open


# ---------------------------------------------------------------------------
# (d) the solver's phases are named in the program
# ---------------------------------------------------------------------------

SCOPES = ("lbfgs/two_loop", "lbfgs/line_search", "lbfgs/value_and_grad",
          "lbfgs/history_update", "lr/forward_loss")


@pytest.mark.parametrize("n_classes", [2, 3])
def test_slice_program_names_the_solver_phases(n_classes):
    from skdist_tpu.models.linear import _freeze

    rng = np.random.RandomState(0)
    X = rng.normal(size=(60, 5)).astype(np.float32)
    y = rng.randint(0, n_classes, size=60)
    est = LogisticRegression(max_iter=20, engine="xla")
    data, meta = est._prep_fit_data(X, y, None)
    static = _freeze(est._static_config(meta))
    init = LogisticRegression._build_fit_slice_kernels(
        meta, static, 4)["init"]
    hyper = {"C": jnp.float32(1.0), "tol": jnp.float32(1e-4)}
    lowered = jax.jit(init).lower(data["X"], data["y"], data["sw"], hyper)
    text = lowered.as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in text, scope
