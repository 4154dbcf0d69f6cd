"""
chip_smoke.py — the quickest proof that the system starts on a TPU.

One process, one chip, JAX first touched here, no child that needs the
device. It drives the program's main paths once through the entry
points a user calls, at the full width of the repo's own headline
shapes, on seeded synthetic data (there is no network), and checks
each phase against a reference that does not share its code:

  search         DistGridSearchCV over LogisticRegression, 96 C x 5
                 folds on 11,314 x 4,096, 20 classes (bench.py's
                 headline), run twice: cold, warm
  forest         DistRandomForestClassifier, 256 trees of depth 8 on
                 200,000 x 28, binary
  boosting       DistHistGradientBoostingClassifier on the same rows
  sparse         LogisticRegression on packed CSR
  kernels        ops.pallas_hist called directly, compiled, against
                 the XLA form of its contraction
  batch_predict  1,000,000 x 64 rows, 10 classes
  serving        an in-process ServingEngine with f32, bf16 and int8
                 registrations

It prints one JSON line per phase (shapes, seconds cold and warm,
compile counts, round stats, resolved engines, device memory, fault
counters) and, as the LAST line of its standard output, exactly

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

It is not a benchmark: the seconds are observations of one run.

The run FAILS — non-zero exit, ``"ok": false`` with the reason, never
``"ok": true`` — when JAX's first device is not a TPU (no option or
environment variable makes the script accept a CPU), when any phase
raises, and when the work could have gone somewhere else than the
chip: a fault counter moved, a fit was error-scored, a round ran out of
memory and shrank, a compacted dispatch fell back to the classic
kernel, the export tier gave up, a search or predict took the host
path, or a Pallas program was not compiled.

``--four-chips`` runs ONLY the fan-out of fits across devices and what
it is compared with: the search grid on a 1D ``tasks`` mesh over all
four devices, on a 2x2 ``tasks x data`` mesh, and on one device; its
last line carries ``"count": 4``.

The phases are functions of their shapes: ``tests/test_chip_smoke.py``
imports this module and calls them small on the CPU.
"""

import argparse
import contextlib
import json
import pickle
import re
import sys
import time
import warnings

import numpy as np

#: fault counters that mean a round, a lane or an exception was
#: absorbed on the way (``skdist_tpu.parallel.faults.FAULT_COUNTERS``)
WATCHED_FAULTS = (
    "rounds_retried", "retries_exhausted", "lanes_quarantined",
    "suppressed", "watchdog_trips", "elastic_shrinks",
)
#: warnings that mean the work left its path; raised as errors
_FATAL_WARNINGS = (
    "falling back",                 # compacted -> classic, export tier
    "exhausted device memory",      # reactive OOM shrink of a round
)
#: compile-shaped counters of ``compile_cache.snapshot()``
_COMPILE_KEYS = ("kernel_misses", "jit_misses", "aot_misses",
                 "aot_export_writes", "aot_export_hits")

# parity budgets, stated before the chip run they judge. The reference
# is sklearn's float64 L-BFGS on the same folds: an independent solver,
# so bench.py's 1e-5 (its budget between two dispatch paths of ONE f32
# solver) does not apply — a CPU rehearsal of the same comparisons at
# full size (XLA-CPU f32 vs sklearn) read 4.0e-3 and 3.2e-5.
#: fold accuracy of the headline's own candidates (max_iter=30,
#: tol=1e-4, C at both ends and the middle, all folds) vs sklearn at the
#: same settings: two solvers stopped at 30 iterations sit on different
#: unconverged iterates where C is large, and one flipped test row
#: reads as 1/2,263 = 4.4e-4
HEADLINE_ACCURACY_BUDGET = 0.01
#: converged (max_iter=200, tol=1e-6) fold neg_log_loss at C=0.1 vs
#: sklearn run to its own convergence: what is left is the f32 solve's
#: stopping error — reduced-precision matmuls on the chip would read
#: ~1e-3 here
CONVERGED_LOGLOSS_BUDGET = 2e-4
#: train accuracy, read on a row sample, of the forest vs sklearn's
#: forest at the same settings on the same rows (different bootstrap
#: streams; 32 quantile bins against exact thresholds). CPU rehearsal
#: at full size: 0.829 (32 trees, exact 'scatter') and 0.835 (64 trees,
#: host C engine) against sklearn's 0.837
FOREST_ACCURACY_MARGIN = 0.015
#: holdout log-loss of the boosted ensemble vs sklearn's
#: HistGradientBoostingClassifier at the same settings
BOOSTING_LOGLOSS_MARGIN = 0.03
#: probabilities / scores of f32 device kernels vs a float64 numpy
#: reference ('highest' matmul precision on the chip)
F32_PROBA_ATOL = 2e-5


class SmokeFailure(AssertionError):
    """A phase's check did not hold. ``partial`` is what the phase had
    measured by then; it is printed with the failure."""

    def __init__(self, msg, partial=None):
        super().__init__(msg)
        self.partial = partial


def check(cond, msg, partial=None):
    if not cond:
        raise SmokeFailure(msg, partial)


def emit(payload):
    print(json.dumps(payload, default=_jsonable), flush=True)


def _jsonable(x):
    if isinstance(x, np.generic):
        return x.item()
    if isinstance(x, np.ndarray):
        return x.tolist()
    return str(x)


# ---------------------------------------------------------------------------
# seeded data (the generators of bench.py / benchmarks/run_all.py)
# ---------------------------------------------------------------------------

def make_text_shaped(seed, n, d, k):
    """Hashed-text-like dense problem: ~1% positive entries, power-law
    column popularity, linearly separable-ish classes."""
    rng = np.random.RandomState(seed)
    col_pop = rng.zipf(1.5, size=d).astype(np.float64)
    cum = np.cumsum(col_pop / col_pop.sum())
    nnz_per_row = max(8, int(0.01 * d))
    cols = np.searchsorted(cum, rng.rand(n, nnz_per_row))
    X = np.zeros((n, d), dtype=np.float32)
    rows = np.repeat(np.arange(n), nnz_per_row)
    X[rows, cols.ravel()] = (
        rng.rand(n * nnz_per_row).astype(np.float32) + 0.5
    )
    W = rng.normal(size=(d, k)).astype(np.float32)
    y = np.argmax(X @ W + 2.0 * rng.normal(size=(n, k)), axis=1)
    return X, y


def make_tabular(seed, n, d, k, noise=0.7):
    rng = np.random.RandomState(seed)
    W = rng.normal(size=(d, k))
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = np.argmax(X @ W + noise * rng.normal(size=(n, k)), axis=1)
    return X, y


# ---------------------------------------------------------------------------
# what every phase line carries, and what fails the run between phases
# ---------------------------------------------------------------------------

def device_fields():
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def memory_fields(devices=None):
    """Free and peak device memory as the runtime reports it (None on a
    backend without ``memory_stats``)."""
    import jax

    out = []
    for dev in devices or jax.devices():
        stats = dev.memory_stats() or {}
        out.append({
            "bytes_limit": stats.get("bytes_limit"),
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        })
    return out


def compile_delta(before, after):
    return {k: after[k] - before[k] for k in _COMPILE_KEYS}


def check_no_compiles(delta, what):
    """A warm path builds, traces and compiles nothing new (reading the
    export tier's files is no compile)."""
    new = {k: v for k, v in delta.items() if k != "aot_export_hits" and v}
    check(not new, f"{what} compiled: {new}")


def check_fault_counters(snapshot):
    """No watched fault counter may have moved: each one means a round,
    a lane or an exception was absorbed instead of surfacing."""
    moved = {k: snapshot[k] for k in WATCHED_FAULTS if snapshot.get(k)}
    check(not moved, f"fault counters moved: {moved}")


def check_rounds(stats, n_tasks, what):
    """The dispatch went through the backend's round loop on the mesh
    (not the host fan-out), covered every task, and retried nothing."""
    check(stats is not None and stats.get("tasks") == n_tasks,
          f"{what}: no device dispatch of {n_tasks} tasks on the "
          f"backend (last_round_stats={stats}) — the host path ran")
    check(not stats.get("retries"), f"{what}: rounds retried: {stats}")


@contextlib.contextmanager
def strict_warnings():
    """Warnings that mean the work left its path become errors; the
    proactive round sizing's notice (the backend picking a round size
    from free memory before the first dispatch) is collected and
    printed, since that IS the path."""
    from skdist_tpu.distribute.search import FitFailedWarning

    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        warnings.filterwarnings("error", category=FitFailedWarning)
        for pat in _FATAL_WARNINGS:
            warnings.filterwarnings("error", message=f".*{pat}")
        yield seen


def round_fields(backend):
    stats = dict(backend.last_round_stats or {})
    return {k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in stats.items()}


# ---------------------------------------------------------------------------
# search — the main path, at full width
# ---------------------------------------------------------------------------

def _finite_cv_results(cv_results, what):
    for key, col in cv_results.items():
        arr = np.asarray(col)
        if arr.dtype.kind in "fiu":
            check(np.all(np.isfinite(arr)),
                  f"{what}: cv_results_[{key!r}] is not finite")


def _grid_search(backend, X, y, grid, est, scoring):
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.parallel import compile_cache

    snap = compile_cache.snapshot()
    t0 = time.perf_counter()
    gs = DistGridSearchCV(
        est, grid, backend=backend, cv=5, scoring=scoring,
        error_score="raise",
    ).fit(X, y)
    wall = time.perf_counter() - t0
    n_fits = len(grid["C"]) * 5
    check_rounds(backend.last_round_stats, n_fits, "search")
    _finite_cv_results(gs.cv_results_, "search")
    return gs, wall, compile_delta(snap, compile_cache.snapshot())


def _split_scores(cv_results, cand):
    return np.array([cv_results[f"split{s}_test_score"][cand]
                     for s in range(5)], dtype=np.float64)


def _sklearn_fold_scores(X, y, Cs, max_iter, tol, scoring):
    """The independent reference: sklearn's float64 L-BFGS logistic
    regression on the same stratified folds DistGridSearchCV cuts."""
    from sklearn.linear_model import LogisticRegression as SkLR
    from sklearn.metrics import log_loss
    from sklearn.model_selection import StratifiedKFold

    labels = np.unique(y)
    out = np.zeros((len(Cs), 5))
    for s, (tr, te) in enumerate(StratifiedKFold(n_splits=5).split(X, y)):
        for i, C in enumerate(Cs):
            sk = SkLR(C=C, max_iter=max_iter, tol=tol).fit(X[tr], y[tr])
            if scoring == "accuracy":
                out[i, s] = float(np.mean(sk.predict(X[te]) == y[te]))
            else:
                out[i, s] = -log_loss(
                    y[te], sk.predict_proba(X[te]), labels=labels
                )
    return out


def phase_search(seed=0, n=11314, d=4096, k=20, n_candidates=96,
                 accuracy_budget=HEADLINE_ACCURACY_BUDGET,
                 logloss_budget=CONVERGED_LOGLOSS_BUDGET):
    """The 480-fit headline, cold then warm, with its parity checks.
    A cut for time cuts ``n_candidates``, never n, d or k. The budgets
    belong to the full shape (one flipped row of a 2,263-row test fold
    is 4.4e-4); a caller at another shape passes its own."""
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.parallel import TPUBackend

    X, y = make_text_shaped(seed, n, d, k)
    Cs = list(np.logspace(-3, 2, n_candidates))
    est = LogisticRegression(max_iter=30, tol=1e-4)

    bk_cold = TPUBackend()
    gs_cold, cold_s, cold_compiles = _grid_search(
        bk_cold, X, y, {"C": Cs}, est, "accuracy")
    bk_warm = TPUBackend()
    gs, warm_s, warm_compiles = _grid_search(
        bk_warm, X, y, {"C": Cs}, est, "accuracy")
    check_no_compiles(warm_compiles, "the warm search")
    warm_vs_cold = float(np.max(np.abs(
        gs.cv_results_["mean_test_score"]
        - gs_cold.cv_results_["mean_test_score"])))
    check(warm_vs_cold == 0.0,
          f"cold and warm cv_results_ differ by {warm_vs_cold}")

    # the headline's own candidates, both ends and the middle, all folds
    sample = sorted({0, n_candidates // 2, n_candidates - 1})
    ref_acc = _sklearn_fold_scores(
        X, y, [Cs[i] for i in sample], 30, 1e-4, "accuracy")
    got_acc = np.stack([_split_scores(gs.cv_results_, i) for i in sample])
    headline_diff = float(np.max(np.abs(got_acc - ref_acc)))
    check(headline_diff <= accuracy_budget,
          f"sampled candidates differ from sklearn by {headline_diff} "
          f"in fold accuracy (budget {accuracy_budget})")

    # converged, continuous scorer: the numerics, not the solver's path
    sub = [0.01, 0.1, 1.0]
    conv = LogisticRegression(max_iter=200, tol=1e-6)
    gs_conv, conv_s, _ = _grid_search(
        TPUBackend(), X, y, {"C": sub}, conv, "neg_log_loss")
    ref_ll = _sklearn_fold_scores(X, y, [sub[1]], 1000, 1e-7,
                                  "neg_log_loss")[0]
    conv_diff = float(np.max(np.abs(
        _split_scores(gs_conv.cv_results_, 1) - ref_ll)))
    check(conv_diff <= logloss_budget,
          f"converged fold log-loss differs from sklearn by {conv_diff} "
          f"(budget {logloss_budget})")

    # the artifact pickles clean and predicts
    loaded = pickle.loads(pickle.dumps(gs))
    pred = loaded.predict(X[:512])
    check(pred.shape == (min(512, len(y)),)
          and float(np.mean(pred == gs.predict(X[:512]))) == 1.0,
          "the pickled search does not predict like the fitted one")

    return {
        "shape": [int(X.shape[0]), int(X.shape[1]), int(k)],
        "n_fits": len(Cs) * 5, "n_candidates": len(Cs),
        "cut": (None if n_candidates == 96 else
                f"candidates cut 96 -> {n_candidates}; n, d, k full"),
        "cold_s": round(cold_s, 2), "warm_s": round(warm_s, 2),
        "converged_subgrid_s": round(conv_s, 2),
        "cold_compiles": cold_compiles, "warm_compiles": warm_compiles,
        "round_stats_warm": round_fields(bk_warm),
        "best_params": gs.best_params_,
        "best_score": float(gs.best_score_),
        "sampled_C": [Cs[i] for i in sample],
        "headline_vs_sklearn_max_fold_accuracy_diff": headline_diff,
        "headline_accuracy_budget": accuracy_budget,
        "converged_vs_sklearn_max_fold_logloss_diff": conv_diff,
        "converged_logloss_budget": logloss_budget,
    }, (X, y)


# ---------------------------------------------------------------------------
# forest and boosting — the histogram trees
# ---------------------------------------------------------------------------

def phase_forest(seed=2, n=200_000, d=28, n_estimators=256, max_depth=8,
                 sample_rows=20_000, hist_mode="auto"):
    from sklearn.ensemble import RandomForestClassifier as SkRF

    from skdist_tpu.distribute.ensemble import DistRandomForestClassifier
    from skdist_tpu.models.tree import resolve_hist_config
    from skdist_tpu.parallel import TPUBackend, compile_cache

    X, y = make_tabular(seed, n, d, 2)
    # the engine the distributed fit resolves (an in-program XLA
    # algorithm: the host C engine cannot shard over the mesh)
    resolved, hist_block = resolve_hist_config(
        d, 32, hist_mode, allow_native=False)

    def fit():
        backend = TPUBackend()
        snap = compile_cache.snapshot()
        t0 = time.perf_counter()
        rf = DistRandomForestClassifier(
            n_estimators=n_estimators, max_depth=max_depth,
            random_state=0, backend=backend, hist_mode=hist_mode,
        ).fit(X, y)
        wall = time.perf_counter() - t0
        check_rounds(backend.last_round_stats, n_estimators, "forest")
        return rf, wall, compile_delta(snap, compile_cache.snapshot()), \
            round_fields(backend)

    rf, cold_s, cold_compiles, _ = fit()
    rf, warm_s, warm_compiles, rounds = fit()

    # both forests are fitted on ALL rows and read on the same row
    # sample: a reference fitted on the sample alone memorises it (its
    # depth-8 trees hold ~80 rows a leaf there) and reads 8 points high
    rows = np.random.RandomState(seed).choice(
        len(y), size=min(sample_rows, len(y)), replace=False)
    acc = float(np.mean(rf.predict(X[rows]) == y[rows]))
    sk = SkRF(n_estimators=n_estimators, max_depth=max_depth, n_jobs=-1,
              random_state=0).fit(X, y)
    sk_acc = float(np.mean(sk.predict(X[rows]) == y[rows]))
    check(abs(acc - sk_acc) <= FOREST_ACCURACY_MARGIN,
          f"forest train accuracy {acc:.4f} on the row sample is more "
          f"than {FOREST_ACCURACY_MARGIN} from sklearn's {sk_acc:.4f}")
    proba = rf.predict_proba(X[rows[:256]])
    check(proba.shape == (min(256, len(rows)), 2)
          and np.all(np.isfinite(proba))
          and np.allclose(proba.sum(axis=1), 1.0, atol=1e-5),
          "forest predict_proba is not a finite distribution")
    return {
        "shape": [int(X.shape[0]), int(X.shape[1]), 2],
        "n_estimators": n_estimators, "max_depth": max_depth,
        "hist_mode_requested": hist_mode,
        "hist_mode_resolved": resolved, "hist_block": hist_block,
        "cold_s": round(cold_s, 2), "warm_s": round(warm_s, 2),
        "cold_compiles": cold_compiles, "warm_compiles": warm_compiles,
        "round_stats_warm": rounds,
        "train_accuracy_on_sample": acc,
        "sklearn_train_accuracy_on_sample": sk_acc,
        "sample_rows": int(len(rows)),
        "accuracy_margin": FOREST_ACCURACY_MARGIN,
    }, (X, y)


def phase_boosting(seed=2, n=200_000, d=28, max_iter=30, max_depth=5,
                   data=None):
    from sklearn.ensemble import HistGradientBoostingClassifier as SkHGB
    from sklearn.metrics import log_loss

    from skdist_tpu.models.gbdt import DistHistGradientBoostingClassifier
    from skdist_tpu.models.tree import resolve_hist_config

    X, y = data if data is not None else make_tabular(seed, n, d, 2)
    n_tr = int(0.9 * len(y))
    settings = dict(learning_rate=0.1, max_iter=max_iter,
                    max_depth=max_depth, l2_regularization=0.0,
                    min_samples_leaf=20, early_stopping=False,
                    random_state=0)

    def fit():
        t0 = time.perf_counter()
        model = DistHistGradientBoostingClassifier(
            max_bins=64, **settings).fit(X[:n_tr], y[:n_tr])
        return model, time.perf_counter() - t0

    model, cold_s = fit()
    model, warm_s = fit()
    proba = model.predict_proba(X[n_tr:])
    check(np.all(np.isfinite(proba)), "boosting probabilities not finite")
    ll = float(log_loss(y[n_tr:], proba, labels=[0, 1]))
    sk = SkHGB(max_bins=64, max_leaf_nodes=None, **settings).fit(
        X[:n_tr], y[:n_tr])
    sk_ll = float(log_loss(y[n_tr:], sk.predict_proba(X[n_tr:]),
                           labels=[0, 1]))
    check(ll <= sk_ll + BOOSTING_LOGLOSS_MARGIN,
          f"boosting holdout log-loss {ll:.4f} is more than "
          f"{BOOSTING_LOGLOSS_MARGIN} above sklearn's {sk_ll:.4f}")
    return {
        "shape": [int(X.shape[0]), int(X.shape[1]), 2],
        "rounds": int(max_iter), "max_depth": max_depth, "max_bins": 64,
        "n_iter_": int(np.asarray(model.n_iter_)),
        "hist_mode_resolved": resolve_hist_config(
            d, 64, allow_native=False)[0],
        "cold_s": round(cold_s, 2), "warm_s": round(warm_s, 2),
        "holdout_logloss": ll, "sklearn_holdout_logloss": sk_ll,
        "logloss_margin": BOOSTING_LOGLOSS_MARGIN,
    }


# ---------------------------------------------------------------------------
# sparse — packed CSR against the dense fit
# ---------------------------------------------------------------------------

def phase_sparse(X, y):
    """One packed-CSR fit against the dense fit of the same data."""
    import scipy.sparse as sp

    from skdist_tpu import sparse as sx
    from skdist_tpu.models import LogisticRegression

    Xs = sp.csr_matrix(X)
    check(sx.would_pack(Xs), "the CSR input would not route packed: "
          f"{sx.pack_decision(Xs)}")
    # a fit that CONVERGES inside its budget: two f32 trajectories cut
    # off early (C=1 at 100 iterations) sit 2.8e-2 apart in probability
    # on the CPU as well — summation order, not the packing
    kw = dict(C=0.01, max_iter=200, tol=1e-5, engine="xla")

    def fit(data):
        t0 = time.perf_counter()
        model = LogisticRegression(**kw).fit(data, y)
        return model, time.perf_counter() - t0

    packed, packed_cold_s = fit(Xs)
    packed, packed_warm_s = fit(Xs)
    dense, _ = fit(X)
    rows = slice(0, 2048)
    diff = float(np.max(np.abs(
        packed.predict_proba(Xs[rows]) - dense.predict_proba(X[rows]))))
    # two f32 solves of one convex problem through different
    # contractions (gather/scatter vs matmul), each stopped by the same
    # rule (CPU rehearsal at full size: 1.5e-5)
    check(diff <= 1e-3,
          f"packed and dense fits differ by {diff} in probability")
    return {
        "shape": [int(X.shape[0]), int(X.shape[1]), int(len(np.unique(y)))],
        "nnz": int(Xs.nnz), "max_row_nnz": int(np.diff(Xs.indptr).max()),
        "packed_cold_s": round(packed_cold_s, 2),
        "packed_warm_s": round(packed_warm_s, 2),
        "n_iter_packed": int(np.max(np.asarray(packed.n_iter_))),
        "n_iter_dense": int(np.max(np.asarray(dense.n_iter_))),
        "packed_vs_dense_max_proba_diff": diff,
    }


# ---------------------------------------------------------------------------
# kernels — the Pallas program, compiled, against its XLA form
# ---------------------------------------------------------------------------

def phase_kernels(seed=5, hist_shape=(200_000, 28, 64, 32, 2),
                  interpret=False, pallas_forest=(20_000, 16, 6)):
    """``interpret`` is False on the chip — the kernel is compiled and
    shown compiled; the CPU test passes True, the only way a CPU runs
    it."""
    import jax
    import jax.numpy as jnp

    from skdist_tpu.distribute.ensemble import DistRandomForestClassifier
    from skdist_tpu.ops.pallas_hist import level_histogram
    from skdist_tpu.parallel import TPUBackend

    out = {"interpret": bool(interpret), "programs": {}}
    rng = np.random.RandomState(seed)

    def compare(name, pallas_fn, xla_fn, args, rtol):
        pallas_fn = jax.jit(pallas_fn)
        t0 = time.perf_counter()
        got = np.asarray(jax.block_until_ready(pallas_fn(*args)))
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(pallas_fn(*args))
        warm = time.perf_counter() - t0
        want = np.asarray(xla_fn(*args))
        scale = max(float(np.max(np.abs(want))), 1e-12)
        err = float(np.max(np.abs(got - want))) / scale
        check(got.shape == want.shape and np.all(np.isfinite(got)),
              f"{name}: wrong shape or non-finite output")
        check(err <= rtol, f"{name}: differs from the XLA form by "
              f"{err} of the output's scale (budget {rtol})")
        compiled = None
        if not interpret:
            # is the kernel IN the compiled program, as a Mosaic call?
            compiled = "tpu_custom_call" in pallas_fn.lower(
                *args).compile().as_text()
            check(compiled, f"{name}: no tpu_custom_call in the compiled "
                  "program — the Pallas kernel was not compiled")
        out["programs"][name] = {
            "cold_s": round(cold, 3), "warm_s": round(warm, 4),
            "max_rel_err_vs_xla": err, "tpu_custom_call": compiled,
        }

    # --- per-level histogram vs the scatter formulation
    n, d, B, nl, C = hist_shape
    Xb = jnp.asarray(rng.randint(0, B, size=(n, d)).astype(np.int32))
    key = jnp.asarray(rng.randint(0, nl + 1, size=n).astype(np.int32))
    Ych = jnp.asarray(rng.rand(n, C).astype(np.float32))

    def hist_pallas(Xb, key, Ych):
        return level_histogram(Xb, key, Ych, nl=nl, n_bins=B,
                               interpret=interpret)

    def hist_scatter(Xb, key, Ych):
        # ONE scatter-add over (feature, node, bin) segments; samples
        # not at this level go to a spill row that is dropped
        seg = (jnp.arange(d)[None, :] * (nl * B)
               + jnp.minimum(key, nl)[:, None] * B + Xb)  # (n, d)
        seg = jnp.where((key < nl)[:, None], seg, d * nl * B)
        vals = jnp.broadcast_to(Ych[:, None, :], (n, d, C))
        flat = jnp.zeros((d * nl * B + 1, C), jnp.float32).at[
            seg.reshape(-1)].add(vals.reshape(-1, C))
        return flat[:-1].reshape(d, nl, B, C)

    out["hist_shape"] = list(hist_shape)
    # f32 sums of up to n/nl values in [0, 1): the MXU's reduced-pass
    # default rounds each addend to bf16 (~4e-3 relative), the sum
    # averages it down
    compare("level_histogram", hist_pallas, jax.jit(hist_scatter),
            (Xb, key, Ych), rtol=5e-3)

    # --- one small forest through the public path with the Pallas engine
    forest_rows, n_trees, depth = pallas_forest
    Xf, yf = make_tabular(seed, forest_rows, 28, 2)
    backend = TPUBackend()
    t0 = time.perf_counter()
    rf = DistRandomForestClassifier(
        n_estimators=n_trees, max_depth=depth, random_state=0,
        backend=backend, hist_mode="pallas",
    ).fit(Xf, yf)
    pallas_forest_s = time.perf_counter() - t0
    check_rounds(backend.last_round_stats, n_trees, "pallas forest")
    # the XLA twin of the same contraction ('scatter', the other XLA
    # engine, is no reference on a TPU: its compile at 200,000 x 28 did
    # not end in 35 minutes on the v5e)
    ref = DistRandomForestClassifier(
        n_estimators=n_trees, max_depth=depth, random_state=0,
        backend=TPUBackend(), hist_mode="matmul",
    ).fit(Xf, yf)
    same = all(np.array_equal(rf._trees[k], ref._trees[k])
               for k in ("feat", "thr", "is_split"))
    acc = float(np.mean(rf.predict(Xf) == yf))
    ref_acc = float(np.mean(ref.predict(Xf) == yf))
    check(same and acc == ref_acc,
          f"hist_mode='pallas' and 'matmul' grew different forests from "
          f"the same seeds (train accuracy {acc:.4f} vs {ref_acc:.4f}): "
          "both histograms are exact on integer bootstrap counts")
    out["pallas_forest"] = {
        "shape": [forest_rows, 28, 2], "n_estimators": n_trees,
        "max_depth": depth, "fit_s": round(pallas_forest_s, 2),
        "train_accuracy": acc, "matmul_train_accuracy": ref_acc,
        "same_splits_as_matmul": same,
    }
    return out


# ---------------------------------------------------------------------------
# batch predict and serving
# ---------------------------------------------------------------------------

def _scoring_model(seed, n_train=5000, d=64, k=10):
    from skdist_tpu.models import LogisticRegression

    X, y = make_tabular(seed, n_train, d, k)
    return LogisticRegression(max_iter=40, engine="xla").fit(X, y)


def _numpy_proba(model, X):
    """softmax(X @ coef.T + intercept) in float64 on the host."""
    z = (np.asarray(X, np.float64) @ np.asarray(model.coef_, np.float64).T
         + np.asarray(model.intercept_, np.float64))
    z -= z.max(axis=1, keepdims=True)
    e = np.exp(z)
    return e / e.sum(axis=1, keepdims=True)


def phase_batch_predict(seed=3, n_rows=1_000_000, d=64, k=10):
    from skdist_tpu.distribute.predict import batch_predict
    from skdist_tpu.parallel import TPUBackend

    model = _scoring_model(seed, d=d, k=k)
    Xs = np.random.RandomState(seed + 1).rand(n_rows, d).astype(np.float32)

    def run():
        backend = TPUBackend()
        t0 = time.perf_counter()
        proba = batch_predict(model, Xs, method="predict_proba",
                              backend=backend)
        wall = time.perf_counter() - t0
        stats = backend.last_round_stats
        check(stats is not None and stats.get("rounds", 0) >= 1,
              "batch_predict left no round stats on the backend — it "
              "took the host path, not backend.batched_map")
        return proba, wall, round_fields(backend)

    proba, cold_s, _ = run()
    proba, warm_s, rounds = run()
    check(proba.shape == (n_rows, k) and np.all(np.isfinite(proba)),
          f"batch_predict output {proba.shape} is wrong or not finite")
    diff = float(np.max(np.abs(proba - _numpy_proba(model, Xs))))
    check(diff <= F32_PROBA_ATOL,
          f"batch_predict differs from the float64 host reference by "
          f"{diff} (budget {F32_PROBA_ATOL})")
    return {
        "rows": n_rows, "shape": [n_rows, d, k],
        "cold_s": round(cold_s, 2), "warm_s": round(warm_s, 3),
        "round_stats_warm": rounds,
        "max_abs_diff_vs_float64_host": diff, "budget": F32_PROBA_ATOL,
    }, model


def phase_serving(model, seed=7, request_rows=(1, 17, 4096), repeats=6):
    """f32, bf16 and int8 registrations of one model behind one
    in-process engine; a few dozen requests of three sizes."""
    from skdist_tpu.parallel import TPUBackend, compile_cache
    from skdist_tpu.serve import ServingEngine
    from skdist_tpu.serve.registry import DEFAULT_QUANT_PARITY_BOUND

    d = int(model.coef_.shape[1])
    rng = np.random.RandomState(seed)
    tiers = ("float32", "bfloat16", "int8")
    out = {"request_rows": list(request_rows), "tiers": {}}
    engine = ServingEngine(backend=TPUBackend(),
                           max_batch_rows=max(request_rows))
    try:
        t0 = time.perf_counter()
        entries = {
            dt: engine.register(f"m-{dt}", model,
                                methods=("predict", "predict_proba"),
                                serve_dtype=dt)
            for dt in tiers
        }
        out["register_s"] = round(time.perf_counter() - t0, 2)
        for dt, entry in entries.items():
            check(entry.device, f"{dt} entry serves from the host path")
        snap = compile_cache.snapshot()
        n_requests = 0
        for dt in tiers:
            # each tier's own gate: what its registration probe measured
            # is the class of error it may show (f32: f32 rounding)
            bound = (F32_PROBA_ATOL if dt == "float32"
                     else DEFAULT_QUANT_PARITY_BOUND)
            worst, agree, lat = 0.0, 1.0, []
            for rows in request_rows:
                for _ in range(repeats):
                    Xq = rng.rand(rows, d).astype(np.float32)
                    want = _numpy_proba(model, Xq)
                    t0 = time.perf_counter()
                    proba = engine.predict_proba(
                        Xq, model=f"m-{dt}", timeout_s=60.0)
                    label = engine.predict(
                        Xq, model=f"m-{dt}", timeout_s=60.0)
                    lat.append((time.perf_counter() - t0) / 2)
                    n_requests += 2
                    check(proba.shape == want.shape
                          and label.shape == (rows,),
                          f"{dt}: wrong output shape for {rows} rows")
                    worst = max(worst, float(np.max(np.abs(proba - want))))
                    agree = min(agree, float(np.mean(
                        label == model.classes_[want.argmax(axis=1)])))
            check(worst <= bound,
                  f"{dt}: probabilities differ from the float64 host "
                  f"reference by {worst} (its gate: {bound})")
            check(agree == 1.0 if dt == "float32" else agree >= 0.95,
                  f"{dt}: predicted labels agree on {agree:.3f} of rows")
            out["tiers"][dt] = {
                "registration_parity": entries[dt].quant_error,
                "params_nbytes": entries[dt].params_nbytes,
                "max_abs_proba_diff_vs_float64_host": worst,
                "gate": bound, "label_agreement": agree,
                "median_request_s": round(float(np.median(lat)), 5),
            }
        post_warm = compile_delta(snap, compile_cache.snapshot())
        check_no_compiles(post_warm, "serving, after registration,")
        stats = engine.stats()
        out["n_requests"] = n_requests
        out["compiles_after_warmup"] = stats.get("compiles_after_warmup")
        out["post_registration_compiles"] = post_warm
    finally:
        engine.close(drain=True)
    check(engine.closed and engine.queue_depth() == 0,
          "close(drain=True) left requests queued")
    return out


# ---------------------------------------------------------------------------
# four chips — the fan-out of fits across devices, and nothing else
# ---------------------------------------------------------------------------

def _shard_devices(backend, n_tasks):
    """Which devices hold a shard of a task argument this backend
    places — code that has only seen virtual devices may put everything
    on the first."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    arr = jax.device_put(
        np.zeros((n_tasks,), np.float32),
        NamedSharding(backend.mesh, P(backend.axis_name)),
    )
    return sorted({s.device.id for s in arr.addressable_shards
                   if s.data.size})


_ALL_REDUCE = re.compile(r"= \(?(\w+)\[[^=]*? all-reduce(?:-start)?\(.*?"
                         r"replica_groups=(\[\d+,(\d+)\]|\{\{([\d,]+)\})")


def _all_reduces(executables):
    """``{"<dtype>/<group size>": count}`` over the all-reduce ops in
    the compiled programs' text: WHICH devices reduce WHAT. A float
    all-reduce over groups of ``data_axis_size`` devices is the psum of
    gradient/loss partials along the 'data' axis; the ``pred`` one over
    every device is only the lanes' "all done" flag."""
    found = {}
    for exe in executables:
        for line in exe.as_text().splitlines():
            m = _ALL_REDUCE.search(line)
            if m:
                size = m.group(3) or str(len(m.group(4).split(",")))
                key = f"{m.group(1)}/{size}"
                found[key] = found.get(key, 0) + 1
    return found


def phase_four_chips(seed=0, n=11314, d=4096, k=20, n_candidates=24,
                     devices=None):
    """The search grid on a 1D tasks mesh over every device, on a 2x2
    tasks x data mesh, and on one device. Cut for time in candidates
    only."""
    import jax

    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.parallel import TPUBackend, compile_cache

    devices = list(devices or jax.devices())
    check(len(devices) == 4, f"the four-chip phase needs 4 devices; "
          f"it was given {len(devices)}")
    X, y = make_text_shaped(seed, n, d, k)
    Cs = list(np.logspace(-3, 2, n_candidates))
    est = LogisticRegression(max_iter=30, tol=1e-4)
    idle_peak = [m["peak_bytes_in_use"] for m in memory_fields(devices)]
    layouts = {
        "tasks_1d": lambda: TPUBackend(devices=devices),
        "tasks_x_data_2x2": lambda: TPUBackend(devices=devices,
                                               data_axis_size=2),
        "one_device": lambda: TPUBackend(devices=devices[:1]),
    }
    out = {"shape": [n, d, k], "n_fits": n_candidates * 5,
           "cut": f"candidates cut 96 -> {n_candidates}; n, d, k full",
           "idle_peak_bytes": idle_peak, "layouts": {}}
    conv = LogisticRegression(max_iter=200, tol=1e-6)
    sub = [0.01, 0.1, 1.0]
    scores, conv_scores = {}, {}
    for name, make in layouts.items():
        n_before = len(compile_cache.aot_executables())
        backend = make()
        gs, cold_s, cold_compiles = _grid_search(
            backend, X, y, {"C": Cs}, est, "accuracy")
        # the programs THIS layout compiled: what does the compiler
        # reduce over the mesh in them?
        all_reduce = _all_reduces(
            compile_cache.aot_executables()[n_before:])
        backend = make()
        gs, warm_s, warm_compiles = _grid_search(
            backend, X, y, {"C": Cs}, est, "accuracy")
        scores[name] = np.stack(
            [_split_scores(gs.cv_results_, i) for i in range(len(Cs))])
        gs_conv, conv_s, _ = _grid_search(
            make(), X, y, {"C": sub}, conv, "neg_log_loss")
        conv_scores[name] = np.stack(
            [_split_scores(gs_conv.cv_results_, i) for i in range(len(sub))])
        out["layouts"][name] = {
            "mesh": dict(zip(backend.mesh.axis_names,
                             backend.mesh.devices.shape)),
            "cold_s": round(cold_s, 2), "warm_s": round(warm_s, 2),
            "converged_subgrid_s": round(conv_s, 2),
            "cold_compiles": cold_compiles, "warm_compiles": warm_compiles,
            "round_stats_warm": round_fields(backend),
            "task_shard_devices": _shard_devices(backend, 8),
            "all_reduces_in_compiled_text": all_reduce,
            "best_params": gs.best_params_,
        }
        if name != "one_device":
            held = out["layouts"][name]["task_shard_devices"]
            check(held == sorted(dv.id for dv in devices),
                  f"{name}: task shards live on devices {held}, not on "
                  "all four", out)
    peak = [m["peak_bytes_in_use"] for m in memory_fields(devices)]
    out["peak_bytes"] = peak
    if devices[0].platform == "tpu":
        # (the CPU backend of the tests reports no memory stats)
        for dev, before, after in zip(devices, idle_peak, peak):
            check(after is not None and after > (before or 0) + (64 << 20),
                  f"device {dev.id} peak memory {after} never rose "
                  f"above idle {before}: it did no work", out)
    # a layout changes summation order. The headline's fits stop at 30
    # iterations, so two layouts are two unconverged trajectories — the
    # same budget as against sklearn at those settings; the converged
    # sub-grid holds them to the numerics budget
    for name in ("tasks_x_data_2x2", "one_device"):
        diff = float(np.max(np.abs(scores[name] - scores["tasks_1d"])))
        conv_diff = float(np.max(np.abs(
            conv_scores[name] - conv_scores["tasks_1d"])))
        out["layouts"][name].update(
            max_fold_accuracy_diff_vs_tasks_1d=diff,
            converged_max_fold_logloss_diff_vs_tasks_1d=conv_diff)
    for name in ("tasks_x_data_2x2", "one_device"):
        got = out["layouts"][name]
        check(got["max_fold_accuracy_diff_vs_tasks_1d"]
              <= HEADLINE_ACCURACY_BUDGET,
              f"{name} cv_results_ differ from the 1D mesh by "
              f"{got['max_fold_accuracy_diff_vs_tasks_1d']} in fold "
              f"accuracy (budget {HEADLINE_ACCURACY_BUDGET})", out)
        check(got["converged_max_fold_logloss_diff_vs_tasks_1d"]
              <= CONVERGED_LOGLOSS_BUDGET,
              f"{name} converged cv_results_ differ from the 1D mesh by "
              f"{got['converged_max_fold_logloss_diff_vs_tasks_1d']} in "
              f"fold log-loss (budget {CONVERGED_LOGLOSS_BUDGET})", out)
    found = out["layouts"]["tasks_x_data_2x2"]["all_reduces_in_compiled_text"]
    check(any(key == "f32/2" for key in found),
          "no float all-reduce over pairs of devices in the programs the "
          f"2x2 mesh compiled ({found}): the 'data' axis does not reduce",
          out)
    found = out["layouts"]["one_device"]["all_reduces_in_compiled_text"]
    check(not found, f"an all-reduce in a one-device program: {found}", out)
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run_phases(phases):
    """Run ``(name, thunk)`` phases in order. Every phase line carries
    its seconds, device memory and the fault counters; a moved fault
    counter or a fatal warning ends the run at the phase that caused
    it. A phase's exception always propagates (a failed check is
    printed with what the phase had measured, then re-raised)."""
    from skdist_tpu.parallel import faults

    faults.reset_stats()
    for name, thunk in phases:
        t0 = time.perf_counter()
        with strict_warnings() as seen:
            try:
                result = thunk()
            except SmokeFailure as failure:
                if failure.partial is not None:
                    emit({"phase": name, "failed": str(failure),
                          **failure.partial})
                raise
        snapshot = faults.snapshot()
        emit({
            "phase": name, "seconds": round(time.perf_counter() - t0, 2),
            **result,
            "warnings": sorted({str(w.message)[:200] for w in seen})[:8],
            "memory": memory_fields(),
            "faults": {k: v for k, v in snapshot.items()
                       if v or k in WATCHED_FAULTS},
        })
        check_fault_counters(snapshot)


def one_chip_phases(seed):
    """The seven phases; later ones reuse what earlier ones made (the
    search's data, the tabular rows, the scoring model)."""
    kept = {}

    def search():
        result, kept["text"] = phase_search(seed=seed)
        return result

    def forest():
        result, kept["tabular"] = phase_forest(seed=seed + 2)
        return result

    def batch_predict():
        result, kept["model"] = phase_batch_predict(seed=seed + 3)
        return result

    return [
        ("search", search),
        ("forest", forest),
        ("boosting", lambda: phase_boosting(data=kept["tabular"])),
        ("sparse", lambda: phase_sparse(*kept["text"])),
        ("kernels", lambda: phase_kernels(seed=seed + 5)),
        ("batch_predict", batch_predict),
        ("serving", lambda: phase_serving(kept["model"], seed=seed + 7)),
    ]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fan-out across four devices and "
                         "what it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    try:
        import jax

        device = device_fields()
        if device["platform"] != "tpu":
            raise SmokeFailure(
                f"JAX's first device is {device}, not a TPU")
        want = 4 if args.four_chips else 1
        if device["count"] != want:
            raise SmokeFailure(
                f"this run needs {want} chip(s); jax.devices() has "
                f"{device['count']}")
        from skdist_tpu import native
        from skdist_tpu.parallel import compile_cache

        emit({
            "phase": "start", "device": device,
            "jax": jax.__version__,
            "compile_cache_dir": compile_cache.enable_disk_cache(),
            "native_extensions": native.ext_status(),
            "memory": memory_fields(),
        })
        if args.four_chips:
            phases = [("four_chips",
                       lambda: phase_four_chips(seed=args.seed))]
        else:
            phases = one_chip_phases(args.seed)
        run_phases(phases)
    except Exception as exc:
        import traceback

        traceback.print_exc()
        emit({"ok": False,
              "reason": f"{type(exc).__name__}: {exc}"[:2000]})
        return 1
    emit({"phase": "done",
          "total_s": round(time.perf_counter() - t_start, 1)})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
