"""
DistGridSearchCV / DistRandomizedSearchCV tests.

Mirrors the reference test strategy (skdist/distribute/tests/
test_search.py: tiny deterministic arrays, exact predictions) plus the
new parity tiers: sklearn cv_results_ schema equality on the generic
path and batched-vs-generic agreement (the BASELINE.json 1e-5 target).
"""

import pickle

import numpy as np
import pytest

from skdist_tpu.distribute.search import DistGridSearchCV, DistRandomizedSearchCV
from skdist_tpu.models import LinearSVC, LogisticRegression, Ridge

# the reference's canonical toy problem (test_search.py:38-45)
X_TOY = np.array([[1, 1, 1], [0, 0, 0], [-1, -1, -1]] * 100, dtype=np.float32)
Y_TOY = np.array([0, 0, 1] * 100)


def test_fit_predict_toy():
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=50), {"C": [0.1, 1.0]}, cv=5,
        scoring="f1_weighted",
    ).fit(X_TOY, Y_TOY)
    preds = gs.predict(np.array([[1.0, 1.0, 1.0], [0, 0, 0], [-1, -1, -1]]))
    assert list(preds) == [0, 0, 1]


def test_cv_results_schema_vs_sklearn(clf_data):
    from sklearn.linear_model import LogisticRegression as SkLR
    from sklearn.model_selection import GridSearchCV

    X, y = clf_data
    grid = {"C": [0.01, 1.0, 100.0]}
    ours = DistGridSearchCV(SkLR(max_iter=200), grid, cv=3).fit(X, y)
    sk = GridSearchCV(SkLR(max_iter=200), grid, cv=3).fit(X, y)
    for key in sk.cv_results_:
        assert key in ours.cv_results_, key
    np.testing.assert_allclose(
        ours.cv_results_["mean_test_score"],
        sk.cv_results_["mean_test_score"],
        atol=1e-12,
    )
    assert (
        ours.cv_results_["rank_test_score"] == sk.cv_results_["rank_test_score"]
    ).all()
    assert ours.best_params_ == sk.best_params_
    assert ours.best_index_ == sk.best_index_


def test_batched_matches_generic(clf_data):
    """The 1e-5 north star: device-batched fan-out vs per-task path."""
    from sklearn.metrics import accuracy_score, make_scorer

    X, y = clf_data
    grid = {"C": [0.1, 1.0, 10.0]}
    batched = DistGridSearchCV(
        LogisticRegression(max_iter=100), grid, cv=3, scoring="accuracy"
    ).fit(X, y)
    generic = DistGridSearchCV(
        LogisticRegression(max_iter=100), grid, cv=3,
        scoring=make_scorer(accuracy_score),
    ).fit(X, y)
    np.testing.assert_allclose(
        batched.cv_results_["mean_test_score"],
        generic.cv_results_["mean_test_score"],
        atol=1e-5,
    )


def test_batched_on_device_mesh(clf_data, tpu_backend):
    X, y = clf_data
    grid = {"C": [0.1, 1.0, 10.0], "tol": [1e-4, 1e-3]}
    local = DistGridSearchCV(
        LogisticRegression(max_iter=100), grid, cv=3, scoring="accuracy"
    ).fit(X, y)
    dist = DistGridSearchCV(
        LogisticRegression(max_iter=100), grid, backend=tpu_backend, cv=3,
        scoring="accuracy",
    ).fit(X, y)
    np.testing.assert_allclose(
        local.cv_results_["mean_test_score"],
        dist.cv_results_["mean_test_score"],
        atol=1e-6,
    )
    # backend must be stripped from the fitted artifact
    assert dist.backend is None
    pickle.dumps(dist)


def test_2d_mesh_data_sharding(clf_data):
    """tasks x data 2D mesh: rows of X shard over the 'data' axis while
    tasks fan out over 'tasks'; results must match the 1D mesh."""
    from skdist_tpu.parallel import TPUBackend

    X, y = clf_data
    grid = {"C": [0.1, 1.0, 10.0]}
    flat = DistGridSearchCV(
        LogisticRegression(max_iter=100), grid, backend=TPUBackend(),
        cv=3, scoring="accuracy",
    ).fit(X, y)
    two_d = DistGridSearchCV(
        LogisticRegression(max_iter=100), grid,
        backend=TPUBackend(data_axis_size=2), cv=3, scoring="accuracy",
    ).fit(X, y)
    np.testing.assert_allclose(
        flat.cv_results_["mean_test_score"],
        two_d.cv_results_["mean_test_score"],
        atol=1e-3,
    )


def test_multimetric(clf_data):
    X, y = clf_data
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=100), {"C": [0.1, 1.0]}, cv=3,
        scoring=["accuracy", "f1_weighted"], refit="accuracy",
    ).fit(X, y)
    assert "mean_test_accuracy" in gs.cv_results_
    assert "mean_test_f1_weighted" in gs.cv_results_
    assert hasattr(gs, "best_estimator_")


def test_return_train_score(clf_data):
    X, y = clf_data
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=100), {"C": [1.0]}, cv=3,
        scoring="accuracy", return_train_score=True,
    ).fit(X, y)
    assert "mean_train_score" in gs.cv_results_
    assert gs.cv_results_["mean_train_score"][0] >= gs.cv_results_["mean_test_score"][0] - 0.05


def test_randomized_search(clf_data):
    from scipy.stats import uniform

    X, y = clf_data
    rs = DistRandomizedSearchCV(
        LogisticRegression(max_iter=100),
        {"C": uniform(0.01, 10.0)},
        n_iter=5, random_state=0, cv=3, scoring="accuracy",
    ).fit(X, y)
    assert len(rs.cv_results_["params"]) == 5
    assert rs.score(X, y) > 0.9


def test_randomized_n_iter_capped(clf_data):
    X, y = clf_data
    rs = DistRandomizedSearchCV(
        LogisticRegression(max_iter=50), {"C": [0.1, 1.0]},
        n_iter=10, cv=3, scoring="accuracy",
    ).fit(X, y)
    # reference _check_n_iter caps at grid size (validation.py:99-110)
    assert len(rs.cv_results_["params"]) == 2


def test_regressor_search(reg_data):
    X, y = reg_data
    gs = DistGridSearchCV(
        Ridge(), {"alpha": [0.01, 1.0, 100.0]}, cv=3, scoring="r2"
    ).fit(X, y)
    assert gs.best_score_ > 0.9
    assert gs.best_params_["alpha"] in (0.01, 1.0)


def test_preds_attribute(clf_data):
    X, y = clf_data
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=100), {"C": [1.0]}, cv=3,
        scoring="accuracy", preds=True,
    ).fit(X, y)
    # out-of-fold probabilities, one row per sample (reference search.py:551-560)
    assert gs.preds_.shape == (len(y), 3)


def test_error_score(clf_data):
    from sklearn.metrics import accuracy_score, make_scorer

    X, y = clf_data

    class Exploding(LogisticRegression):
        def fit(self, X, y=None, sample_weight=None):
            raise RuntimeError("boom")

    gs = DistGridSearchCV(
        Exploding(), {"C": [1.0]}, cv=3, refit=False,
        scoring=make_scorer(accuracy_score), error_score=0.0,
    )
    with pytest.warns(Warning):
        gs.fit(X, y)
    assert (gs.cv_results_["mean_test_score"] == 0.0).all()

    gs2 = DistGridSearchCV(
        Exploding(), {"C": [1.0]}, cv=3, refit=False,
        scoring=make_scorer(accuracy_score), error_score="raise",
    )
    with pytest.raises(RuntimeError):
        gs2.fit(X, y)


def test_fit_params_sample_weight_sliced_per_fold(clf_data):
    """Full-length array fit_params are indexed down to each train fold
    (reference _index_param_value, search.py:208-210) — passing
    sample_weight of length n must work, and zero-weighting one class
    must change what the model learns."""
    from sklearn.linear_model import LogisticRegression as SkLR

    X, y = clf_data
    w = np.ones(len(y))
    gs = DistGridSearchCV(
        SkLR(max_iter=200), {"C": [0.1, 1.0]}, cv=3, scoring="accuracy",
    ).fit(X, y, sample_weight=w)
    assert gs.best_score_ > 0.9

    # zero weight on class 2: the searched models never predict it
    w2 = np.where(y == 2, 0.0, 1.0)
    gs2 = DistGridSearchCV(
        SkLR(max_iter=200), {"C": [1.0]}, cv=3, scoring="accuracy",
        preds=True,
    ).fit(X, y, sample_weight=w2)
    assert 2 not in np.argmax(gs2.preds_, axis=1)

    # scalar / non-length-n params pass through untouched
    from skdist_tpu.utils.validation import index_fit_params
    sliced = index_fit_params(
        X, {"sample_weight": w, "flag": True, "arr3": np.ones(3)},
        np.arange(10),
    )
    assert sliced["sample_weight"].shape == (10,)
    assert sliced["flag"] is True and sliced["arr3"].shape == (3,)


def test_batched_sample_weight_matches_generic(clf_data):
    """sample_weight rides the batched device path (fit-only
    weighting, unweighted scoring) and agrees with the generic host
    path to the BASELINE 1e-5 tolerance."""
    from sklearn.metrics import accuracy_score, make_scorer

    X, y = clf_data
    rng = np.random.RandomState(3)
    w = rng.uniform(0.2, 2.0, size=len(y))
    grid = {"C": [0.1, 1.0, 10.0]}
    batched = DistGridSearchCV(
        LogisticRegression(max_iter=100), grid, cv=3, scoring="accuracy",
    ).fit(X, y, sample_weight=w)
    generic = DistGridSearchCV(
        LogisticRegression(max_iter=100), grid, cv=3,
        scoring=make_scorer(accuracy_score),
    ).fit(X, y, sample_weight=w)
    np.testing.assert_allclose(
        batched.cv_results_["mean_test_score"],
        generic.cv_results_["mean_test_score"], atol=1e-5,
    )
    # weighting has teeth on-device: zero-weighting class 2 stops the
    # searched models from ever predicting it
    w0 = np.where(y == 2, 0.0, 1.0)
    gw = DistGridSearchCV(
        LogisticRegression(max_iter=100), {"C": [1.0]}, cv=3,
        scoring="accuracy", preds=True,
    ).fit(X, y, sample_weight=w0)
    assert 2 not in np.argmax(gw.preds_, axis=1)

    # wrong-length weights never reach the device path: the host path's
    # per-task error_score contract reports the failure
    bad = DistGridSearchCV(
        LogisticRegression(max_iter=50), {"C": [1.0]}, cv=3, refit=False,
        scoring="accuracy", error_score=0.0,
    )
    with pytest.warns(Warning):
        bad.fit(X, y, sample_weight=np.ones(7))
    assert (bad.cv_results_["mean_test_score"] == 0.0).all()


def test_batched_timing_is_per_round(clf_data):
    """fit_time columns on the batched path come from measured
    per-round walls, not a uniform smear (round-1 VERDICT weak-4)."""
    X, y = clf_data
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=50), {"C": [0.1, 1.0, 10.0, 100.0]},
        cv=3, scoring="accuracy", partitions=2,
    ).fit(X, y)
    raw = gs.cv_results_["mean_fit_time"]
    assert (raw > 0).all()
    # partitions=2 → two rounds (candidates 0-1 vs 2-3); round 1
    # carries the compile+dispatch warm-up, so the two rounds' measured
    # walls differ — a uniform smear would make all four equal
    assert len(np.unique(np.round(raw, 12))) >= 2


def test_failed_candidate_ranks_last(clf_data):
    """A single failing candidate under the default error_score=np.nan
    must rank LAST, not poison every rank via NaN propagation and get
    silently selected as best (round-1 advisor finding: scipy rankdata
    propagates NaN -> int32 cast -> best_index_ picked the failure)."""
    from sklearn.metrics import accuracy_score, make_scorer

    X, y = clf_data

    class ExplodingAtC100(LogisticRegression):
        def fit(self, X, y=None, sample_weight=None):
            if self.C == 100.0:
                raise RuntimeError("boom")
            return super().fit(X, y, sample_weight=sample_weight)

    gs = DistGridSearchCV(
        ExplodingAtC100(max_iter=100), {"C": [1.0, 100.0]}, cv=3,
        scoring=make_scorer(accuracy_score),
    )
    with pytest.warns(Warning):
        gs.fit(X, y)
    ranks = gs.cv_results_["rank_test_score"]
    means = gs.cv_results_["mean_test_score"]
    failed = int(np.where(np.isnan(means))[0][0])
    working = 1 - failed
    assert ranks[failed] == 2 and ranks[working] == 1
    assert gs.best_params_["C"] == 1.0
    assert gs.best_score_ > 0.5
    # refit trained the WORKING candidate
    assert gs.best_estimator_.C == 1.0


def test_all_candidates_failing_raises(clf_data):
    """When EVERY candidate fails under error_score=np.nan the search
    raises instead of silently returning candidate 0 with
    best_score_=NaN (same contract as eliminate / multi-model)."""
    from sklearn.metrics import accuracy_score, make_scorer

    X, y = clf_data

    class AlwaysExploding(LogisticRegression):
        def fit(self, X, y=None, sample_weight=None):
            raise RuntimeError("boom")

    gs = DistGridSearchCV(
        AlwaysExploding(), {"C": [0.1, 1.0]}, cv=3, refit=False,
        scoring=make_scorer(accuracy_score),
    )
    with pytest.warns(Warning):
        with pytest.raises(RuntimeError, match="All candidate fits failed"):
            gs.fit(X, y)


def test_preds_predict_fallback(clf_data):
    """preds=True with an estimator lacking predict_proba must fall back
    to predict (reference search.py:556-560 try/except contract)."""
    X, y = clf_data
    svc = LinearSVC()
    gs = DistGridSearchCV(
        svc, {"C": [1.0]}, cv=3, scoring="accuracy", preds=True,
    ).fit(X, y)
    assert gs.preds_.shape == (len(y),)
    assert set(np.unique(gs.preds_)) <= set(np.unique(y))


def test_nested_search(clf_data):
    """Meta-inside-meta nesting (reference examples/search/nested.py)."""
    X, y = clf_data
    inner = DistGridSearchCV(
        LogisticRegression(max_iter=50), {"C": [0.1, 1.0]}, cv=2,
        scoring="accuracy",
    )
    from skdist_tpu.base import clone

    outer = clone(inner)
    outer.fit(X, y)
    assert hasattr(outer, "best_estimator_")


def test_refit_false_single_metric_exposes_best(clf_data):
    """sklearn semantics: best_* available for single-metric refit=False
    (regression; reference search.py:538-541)."""
    X, y = clf_data
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=50), {"C": [0.1, 1.0]}, cv=3,
        scoring="accuracy", refit=False,
    ).fit(X, y)
    assert gs.best_params_["C"] in (0.1, 1.0)
    assert 0 <= gs.best_score_ <= 1
    with pytest.raises(AttributeError):
        gs.predict(X)


def test_binary_only_scorer_multiclass_raises(clf_data):
    """scoring='f1' on 3-class data must NOT silently take the device
    path (which would score last-class-only); the host path raises like
    sklearn (regression)."""
    X, y = clf_data
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=50), {"C": [1.0]}, cv=3,
        scoring="f1", error_score="raise",
    )
    with pytest.raises(ValueError):
        gs.fit(X, y)


def test_partitions_rounds_local(clf_data):
    """partitions chunks the batched program into rounds on the local
    backend too (regression: round_size was a silent no-op)."""
    X, y = clf_data
    full = DistGridSearchCV(
        LogisticRegression(max_iter=50), {"C": [0.1, 1.0, 10.0]}, cv=3,
        scoring="accuracy",
    ).fit(X, y)
    rounds = DistGridSearchCV(
        LogisticRegression(max_iter=50), {"C": [0.1, 1.0, 10.0]}, cv=3,
        scoring="accuracy", partitions=3,
    ).fit(X, y)
    np.testing.assert_allclose(
        full.cv_results_["mean_test_score"],
        rounds.cv_results_["mean_test_score"],
        atol=1e-6,
    )


def test_backend_and_template_not_mutated(clf_data, tpu_backend):
    """fit() must not leak state into the user's backend or template
    estimator (regression: round_size mutation + template stripping)."""
    X, y = clf_data
    template = LogisticRegression(max_iter=50)
    gs = DistGridSearchCV(
        template, {"C": [0.1, 1.0]}, backend=tpu_backend, cv=3,
        scoring="accuracy", partitions=2,
    ).fit(X, y)
    assert tpu_backend.round_size is None
    assert gs.estimator is not template
    # a different-sized mesh on the same kernels must not reuse stale
    # shardings (regression: jit cache keyed without the mesh)
    from skdist_tpu.parallel import TPUBackend
    import jax

    half = TPUBackend(devices=jax.devices()[:4])
    gs2 = DistGridSearchCV(
        LogisticRegression(max_iter=50), {"C": [0.1, 1.0]}, backend=half,
        cv=3, scoring="accuracy",
    ).fit(X, y)
    np.testing.assert_allclose(
        gs.cv_results_["mean_test_score"],
        gs2.cv_results_["mean_test_score"],
        atol=1e-6,
    )


def test_pipeline_base_estimator(clf_data):
    """sklearn Pipelines as the searched estimator, with step-addressed
    params (ubiquitous sk-dist usage pattern)."""
    from sklearn.pipeline import Pipeline
    from sklearn.preprocessing import StandardScaler
    from sklearn.linear_model import LogisticRegression as SkLR

    X, y = clf_data
    pipe = Pipeline([("sc", StandardScaler()), ("lr", SkLR(max_iter=200))])
    gs = DistGridSearchCV(
        pipe, {"lr__C": [0.1, 1.0], "sc__with_mean": [True, False]}, cv=2
    ).fit(X, y)
    assert set(gs.best_params_) == {"lr__C", "sc__with_mean"}
    assert gs.score(X, y) > 0.9


def test_verbose_prints(clf_data, capsys):
    X, y = clf_data
    DistGridSearchCV(
        LogisticRegression(max_iter=50), {"C": [1.0]}, cv=2,
        scoring="accuracy", verbose=1,
    ).fit(X, y)
    out = capsys.readouterr().out
    assert "local backend" in out
    assert "Fitting 2 folds" in out


def test_sample_weight_shape_routing(clf_data):
    """Non-1-D sample_weight shapes route correctly (round-2 review):
    (n,1) columns flatten onto the batched path; 0-d and ragged weights
    fall to the host path where error_score applies instead of crashing
    the dispatch guard."""
    X, y = clf_data
    rng = np.random.RandomState(5)
    w = rng.uniform(0.2, 2.0, size=len(y))
    grid = {"C": [0.1, 1.0]}
    flat = DistGridSearchCV(
        LogisticRegression(max_iter=60), grid, cv=3, scoring="accuracy",
    ).fit(X, y, sample_weight=w)
    col = DistGridSearchCV(
        LogisticRegression(max_iter=60), grid, cv=3, scoring="accuracy",
    ).fit(X, y, sample_weight=w.reshape(-1, 1))
    np.testing.assert_allclose(
        col.cv_results_["mean_test_score"],
        flat.cv_results_["mean_test_score"], atol=1e-7,
    )

    # 0-d weight: guard must not crash (len() of unsized object); the
    # host path runs and the estimator broadcasts the scalar — a valid fit
    zd = DistGridSearchCV(
        LogisticRegression(max_iter=30), {"C": [1.0]}, cv=3,
        refit=False, scoring="accuracy",
    ).fit(X, y, sample_weight=np.asarray(2.0))
    assert np.isfinite(zd.cv_results_["mean_test_score"]).all()

    # ragged weights: guard must not crash at dispatch; the host path's
    # per-task error_score contract reports the failure
    bad = DistGridSearchCV(
        LogisticRegression(max_iter=30), {"C": [1.0]}, cv=3,
        refit=False, scoring="accuracy", error_score=0.0,
    )
    with pytest.warns(Warning):
        bad.fit(X, y, sample_weight=[[1.0], [2.0, 3.0]] * (len(y) // 2))
    assert (bad.cv_results_["mean_test_score"] == 0.0).all()


def test_exact_matmuls_flag_honoured():
    """Linear kernels trace under 'highest' matmul precision (the
    batched-vs-generic ≤1e-5 parity contract on TPU); tree kernels opt
    out via _exact_matmuls=False at every consumer site."""
    from skdist_tpu.models import DecisionTreeClassifier
    from skdist_tpu.models.linear import maybe_exact_matmuls

    assert getattr(LogisticRegression, "_exact_matmuls", True) is True
    assert DecisionTreeClassifier._exact_matmuls is False

    marker = lambda: None
    assert maybe_exact_matmuls(DecisionTreeClassifier, marker) is marker
    wrapped = maybe_exact_matmuls(LogisticRegression, marker)
    assert wrapped is not marker and wrapped.__wrapped__ is marker


def test_transform_inverse_transform_delegation():
    """Fitted search delegates transform/inverse_transform to the
    refit best_estimator_ (reference delegation block, search.py:875-908),
    including the unsupervised y=None path."""
    from sklearn.decomposition import PCA

    X = np.random.RandomState(0).normal(size=(100, 6))
    gs = DistGridSearchCV(PCA(), {"n_components": [2, 3]}, cv=3).fit(X)
    Xt = gs.transform(X)
    assert Xt.shape == (100, gs.best_params_["n_components"])
    assert gs.inverse_transform(Xt).shape == X.shape


def test_warm_c_path_continuous_distribution(clf_data):
    """Randomized search with a continuous C distribution rides the
    warm C-path runner (every candidate differs only in C within its
    tol bucket) and must score identically to the pinned-XLA cold run
    at converged settings."""
    from scipy.stats import loguniform

    X, y = clf_data
    space = {"C": loguniform(1e-3, 1e3), "tol": [1e-4, 1e-6]}
    warm = DistRandomizedSearchCV(
        LogisticRegression(max_iter=300, tol=1e-6), space,
        n_iter=8, cv=3, random_state=0,
    ).fit(X, y)
    cold = DistRandomizedSearchCV(
        LogisticRegression(max_iter=300, tol=1e-6, engine="xla"), space,
        n_iter=8, cv=3, random_state=0,
    ).fit(X, y)
    np.testing.assert_allclose(
        np.asarray(warm.cv_results_["mean_test_score"], dtype=float),
        np.asarray(cold.cv_results_["mean_test_score"], dtype=float),
        atol=1e-4,
    )


def test_warm_cpath_capped_candidates_recorded_cold(clf_data):
    """A warm-seeded host-engine fit that stops on max_iter must be
    REFIT COLD before its CV score is recorded — otherwise the capped
    candidate's score depends on which other C values share the grid
    (ADVICE r05 #1).

    The real solver's converge-vs-cap margins are within one L-BFGS-B
    iteration on toy data (fragile across BLAS/scipy versions), so the
    cap is made DETERMINISTIC: a LogisticRegression subclass whose
    warm-seeded fits always report no converged optimum (w_opt=None —
    exactly what the host engine reports on a max_iter stop) while
    cold fits behave normally. Every warm attempt must then be
    followed by a cold refit of the same candidate, and each
    candidate's recorded scores must equal its solo (grid-independent)
    run bitwise."""
    X, y = clf_data
    fit_log = []

    class CapsWhenWarm(LogisticRegression):
        def fit(self, X, y=None, sample_weight=None):
            warm = getattr(self, "_warm_w0", None) is not None
            fit_log.append((float(self.C), warm))
            super().fit(X, y, sample_weight=sample_weight)
            if warm:
                self._w_opt64 = None  # "stopped on max_iter"
            return self

    est = CapsWhenWarm(max_iter=50, engine="host")
    grid_c = [1e-2, 1.0]
    n_splits = 3
    full = DistGridSearchCV(
        est, {"C": grid_c}, cv=n_splits, scoring="accuracy", refit=False,
    ).fit(X, y)
    # per fold: head cold; candidate 2 warm (capped) THEN cold refit
    assert len(fit_log) == n_splits * 3, fit_log
    per_fold = len(fit_log) // n_splits
    for f in range(n_splits):
        chunk = fit_log[f * per_fold:(f + 1) * per_fold]
        assert chunk == [(1e-2, False), (1.0, True), (1.0, False)], chunk
    # recorded scores are the COLD ones: bitwise equal to solo runs
    for c in grid_c:
        solo = DistGridSearchCV(
            est, {"C": [c]}, cv=n_splits, scoring="accuracy", refit=False,
        ).fit(X, y)
        i = [j for j, p in enumerate(full.cv_results_["params"])
             if p["C"] == c][0]
        np.testing.assert_array_equal(
            np.asarray([full.cv_results_[f"split{s}_test_score"][i]
                        for s in range(n_splits)]),
            np.asarray([solo.cv_results_[f"split{s}_test_score"][0]
                        for s in range(n_splits)]),
            err_msg=f"C={c} recorded a grid-dependent (warm-capped) score",
        )


def test_engine_grid_routes_to_generic_path(clf_data, monkeypatch):
    """A searchable 'engine' must be honoured per candidate: such grids
    route to the generic path (each task clones + set_params + fit, so
    each fit resolves its own engine) instead of compiling one engine
    for the whole batched bucket (ADVICE r05 #2)."""
    from skdist_tpu.distribute import search as search_mod
    from skdist_tpu.parallel import TPUBackend

    X, y = clf_data

    def boom(*a, **k):
        raise AssertionError("batched path must not run for engine grids")

    monkeypatch.setattr(search_mod, "_cached_cv_kernel", boom)
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=20),
        {"C": [0.1, 1.0], "engine": ["host", "xla"]},
        backend=TPUBackend(), cv=3, scoring="accuracy",
    ).fit(X, y)
    assert {p["engine"] for p in gs.cv_results_["params"]} == {"host", "xla"}
    assert gs.best_score_ > 0.5


# ---------------------------------------------------------------------------
# the search owns its placed X: one crossing a fit, the refit over the
# operand the rounds ran on (ISSUE 36; the buffers themselves are
# followed in tests/test_data_axis.py)
# ---------------------------------------------------------------------------

@pytest.fixture
def tracing():
    from skdist_tpu.obs import trace as obs_trace

    obs_trace.clear()
    obs_trace.set_enabled(True)
    yield obs_trace
    obs_trace.set_enabled(False)
    obs_trace.clear()


def _refit_and_places(obs_trace):
    """The fit's ``refit`` span and its ``place_shared`` spans, those
    under the refit apart."""
    spans = [e for e in obs_trace.events() if e[1] == "X"]
    refit = next(e for e in spans if e[0] == "refit")
    places = [e for e in spans if e[0] == "place_shared"]
    under = [e for e in places
             if e[5]["parent_id"] == refit[5]["span_id"]]
    return refit, places, under


def _mesh(n_devices):
    import jax

    from skdist_tpu.parallel import TPUBackend

    return TPUBackend(devices=jax.devices()[:n_devices])


def _placed_refit_case(kind, clf_data, binary_data):
    from skdist_tpu.models import GaussianNB, MultinomialNB

    if kind == "binary":
        X, y = binary_data
        return (LogisticRegression(max_iter=60, engine="xla"),
                {"C": [0.1, 1.0, 10.0]}, X, y)
    X, y = clf_data
    if kind == "multinomial":
        return (LogisticRegression(max_iter=60, engine="xla"),
                {"C": [0.1, 1.0, 10.0]}, X, y)
    if kind == "svc":
        return (LinearSVC(max_iter=60, engine="xla"),
                {"C": [0.1, 1.0]}, X, y)
    if kind == "gaussian_nb":
        return GaussianNB(), {"var_smoothing": [1e-9, 1e-3]}, X, y
    return MultinomialNB(), {"alpha": [0.1, 1.0]}, np.abs(X), y


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize(
    "kind", ["binary", "multinomial", "svc", "gaussian_nb", "multinomial_nb"])
def test_placed_refit_is_the_standalone_fit_to_the_bit(
        kind, n_devices, clf_data, binary_data, tracing):
    """The refit over the placed operand runs the program a standalone
    ``fit`` runs, over the same values: every fitted array is bitwise
    that of ``clone(best).set_params(**best_params_).fit(X, y)``, on a
    one-device mesh and over a replica on each of four."""
    from skdist_tpu.base import clone

    est, grid, X, y = _placed_refit_case(kind, clf_data, binary_data)
    gs = DistGridSearchCV(est, grid, cv=3, backend=_mesh(n_devices)).fit(X, y)
    refit, places, under = _refit_and_places(tracing)
    assert refit[5]["x_placed"] is True and len(under) == 1
    assert sum(e[5]["bytes"] == X.nbytes for e in places) == 1
    alone = clone(est).set_params(**gs.best_params_).fit(X, y)
    assert set(alone._params) == set(gs.best_estimator_._params)
    for name, value in alone._params.items():
        np.testing.assert_array_equal(
            gs.best_estimator_._params[name], value, err_msg=name)
    assert (gs.predict(X) == alone.predict(X)).all()


def test_two_static_buckets_place_x_once(clf_data, tracing):
    """Candidates that differ in a static parameter compile apart and
    dispatch apart — over ONE placement of X."""
    X, y = clf_data
    DistGridSearchCV(
        LogisticRegression(max_iter=40, engine="xla"),
        {"C": [0.1, 1.0], "fit_intercept": [True, False]}, cv=3,
        backend=_mesh(2)).fit(X, y)
    refit, places, under = _refit_and_places(tracing)
    alone = [e for e in places if e[5]["bytes"] == X.nbytes]
    whole = [e for e in places if e[5]["bytes"] > X.nbytes]
    assert (len(alone), len(whole), len(under)) == (1, 2, 1)
    assert under[0][5]["bytes"] == 8 * len(y)


def test_auto_engine_on_cpu_still_refits_on_the_host_engine(
        clf_data, tracing):
    """Under ``engine='auto'`` on a CPU platform ``fit`` takes the
    float64 host engine; the hand-over must not turn that refit into a
    device fit: nothing is placed under ``refit``."""
    from skdist_tpu.models.host_linear import host_engine_available

    if not host_engine_available():
        pytest.skip("no host engine here")
    X, y = clf_data
    est = LogisticRegression(max_iter=60)
    assert est.engine == "auto" and est._resolve_host_engine()
    gs = DistGridSearchCV(
        est, {"C": [0.1, 1.0]}, cv=3, backend=_mesh(1)).fit(X, y)
    refit, places, under = _refit_and_places(tracing)
    assert refit[5] == {**refit[5], "x_placed": False} and not under
    assert "bytes" not in refit[5]
    # the rounds did run over a placed X
    assert sum(e[5]["bytes"] == X.nbytes for e in places) == 1
    alone = LogisticRegression(max_iter=60, **gs.best_params_).fit(X, y)
    np.testing.assert_array_equal(gs.best_estimator_.coef_, alone.coef_)


@pytest.mark.parametrize("case", ["more_fit_params", "tree_family",
                                  "local_backend", "elastic_backend"])
def test_refit_falls_back_to_best_fit(case, clf_data, tracing):
    """What the search cannot hand over keeps ``best.fit``: fit params
    beyond one full-length ``sample_weight`` (no batched path), a
    family without the placed-fit entry (its rounds do run over the
    X the search placed, which is let go before ``best.fit`` places
    its own), a backend without a mesh, and an elastic backend, whose
    mesh may change under the fit."""
    import jax

    from skdist_tpu.models import DecisionTreeClassifier
    from skdist_tpu.parallel import LocalBackend, TPUBackend

    X, y = clf_data
    est = LogisticRegression(max_iter=40, engine="xla")
    grid, fit_params, backend = {"C": [0.1, 1.0]}, {}, _mesh(1)
    if case == "more_fit_params":
        fit_params = {"sample_weight": np.ones(len(y)),
                      "intercept_init": None}
    elif case == "tree_family":
        est, grid = DecisionTreeClassifier(), {"max_depth": [2, 3]}
    elif case == "local_backend":
        backend = LocalBackend()
    else:
        backend = TPUBackend(devices=jax.devices()[:2], elastic=True)
    gs = DistGridSearchCV(est, grid, cv=3, backend=backend).fit(
        X, y, **fit_params)
    refit, places, under = _refit_and_places(tracing)
    assert refit[5]["x_placed"] is False and not under
    assert sum(e[5]["bytes"] == X.nbytes for e in places) == (
        case == "tree_family")
    assert gs.predict(X).shape == y.shape


def test_no_placed_operand_outlives_the_fit(clf_data):
    """After ``fit`` — and after a ``fit`` that raised — the search
    holds no operand, placed or packed, and pickles as before."""
    X, y = clf_data
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=40, engine="xla"), {"C": [0.1, 1.0]},
        cv=3, backend=_mesh(2)).fit(X, y)
    assert not hasattr(gs, "_rounds_X_") and gs.backend is None
    loaded = pickle.loads(pickle.dumps(gs))
    assert (loaded.predict(X) == gs.predict(X)).all()
    failing = DistGridSearchCV(
        LogisticRegression(max_iter=40, engine="xla"), {"C": [0.1, 1.0]},
        cv=3, backend=_mesh(2), refit="no_such_metric",
        scoring={"a": "accuracy"})
    with pytest.raises(ValueError):
        failing.fit(X, y)
    assert not hasattr(failing, "_rounds_X_")
