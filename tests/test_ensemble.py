"""
Tree / forest kernel and Dist* ensemble tests (reference:
skdist/distribute/tests/test_ensemble.py — test_rfc..test_rte with
exact prediction/shape asserts on tiny data).
"""

import pickle

import numpy as np
import pytest

from skdist_tpu.distribute.ensemble import (
    DistExtraTreesClassifier,
    DistExtraTreesRegressor,
    DistForestClassifier,
    DistForestRegressor,
    DistRandomForestClassifier,
    DistRandomForestRegressor,
    DistRandomTreesEmbedding,
)
from skdist_tpu.models import (
    DecisionTreeClassifier,
    DecisionTreeRegressor,
    RandomForestClassifier,
)

# the reference's canonical toy problem
X_TOY = np.array([[1, 1, 1], [0, 0, 0], [-1, -1, -1]] * 100, dtype=np.float32)
Y_TOY = np.array([0, 0, 1] * 100)
X_PRED = np.array([[1.0, 1.0, 1.0], [0, 0, 0], [-1, -1, -1]], dtype=np.float32)


def test_decision_tree_classifier(clf_data):
    from sklearn.tree import DecisionTreeClassifier as SkDT

    from sklearn.datasets import make_classification

    X, y = clf_data
    ours = DecisionTreeClassifier(max_depth=5).fit(X, y)
    sk = SkDT(max_depth=5, random_state=0).fit(X, y)
    assert ours.score(X, y) >= sk.score(X, y) - 0.05
    assert ours.predict_proba(X).shape == (len(y), 3)
    # importances identify the same informative features (needs a
    # problem where features genuinely differ in information)
    Xi, yi = make_classification(
        n_samples=600, n_features=20, n_informative=5, n_redundant=0,
        n_classes=3, random_state=0,
    )
    Xi = Xi.astype(np.float32)
    oi = DecisionTreeClassifier(max_depth=5).fit(Xi, yi)
    si = SkDT(max_depth=5, random_state=0).fit(Xi, yi)
    assert np.corrcoef(
        oi.feature_importances_, si.feature_importances_
    )[0, 1] > 0.7


def test_decision_tree_regressor(reg_data):
    X, y = reg_data
    ours = DecisionTreeRegressor(max_depth=6).fit(X, y)
    assert ours.score(X, y) > 0.5


def test_tree_sample_weight_masking(clf_data):
    """Zero-weight rows must not influence the tree (the fold-mask
    contract every distributed meta-estimator relies on)."""
    X, y = clf_data
    w = np.ones(len(y), dtype=np.float32)
    w[y == 2] = 0.0
    t = DecisionTreeClassifier(max_depth=5).fit(X, y, sample_weight=w)
    preds = t.predict(X[y != 2])
    assert set(np.unique(preds)) <= {0, 1}


def test_rfc_toy():
    rf = DistRandomForestClassifier(
        n_estimators=10, max_depth=4, random_state=0
    ).fit(X_TOY, Y_TOY)
    assert list(rf.predict(X_PRED)) == [0, 0, 1]
    proba = rf.predict_proba(X_PRED)
    assert proba.shape == (3, 2)


def test_rfc_vs_sklearn(clf_data):
    from sklearn.ensemble import RandomForestClassifier as SkRF

    X, y = clf_data
    ours = DistRandomForestClassifier(
        n_estimators=40, max_depth=6, random_state=0
    ).fit(X, y)
    sk = SkRF(n_estimators=40, max_depth=6, random_state=0).fit(X, y)
    assert ours.score(X, y) >= sk.score(X, y) - 0.05


def test_rfr(reg_data):
    X, y = reg_data
    rf = DistRandomForestRegressor(
        n_estimators=30, max_depth=7, random_state=0
    ).fit(X, y)
    assert rf.score(X, y) > 0.6
    assert rf.predict(X).shape == (len(y),)


def test_etc_etr(clf_data, reg_data):
    X, y = clf_data
    etc = DistExtraTreesClassifier(
        n_estimators=30, max_depth=6, random_state=0
    ).fit(X, y)
    assert etc.score(X, y) >= 0.9
    Xr, yr = reg_data
    etr = DistExtraTreesRegressor(
        n_estimators=30, max_depth=7, random_state=0
    ).fit(Xr, yr)
    assert etr.score(Xr, yr) > 0.5


def test_rte(clf_data):
    X, y = clf_data
    rte = DistRandomTreesEmbedding(
        n_estimators=8, max_depth=4, random_state=0
    )
    emb = rte.fit_transform(X)
    assert emb.shape == (len(y), 8 * (2**5 - 1))
    # exactly one active leaf per (sample, tree)
    assert (np.asarray(emb.sum(axis=1)).ravel() == 8).all()
    emb2 = rte.transform(X)
    assert (emb != emb2).nnz == 0


def test_forest_on_mesh(clf_data, tpu_backend):
    X, y = clf_data
    # pin the XLA engine on both sides: this test is about backend
    # invariance of the device kernel (local 'auto' would pick the
    # host C engine, whose PRNG streams legitimately differ)
    local = DistRandomForestClassifier(
        n_estimators=16, max_depth=5, random_state=0, hist_mode="scatter"
    ).fit(X, y)
    dist = DistRandomForestClassifier(
        n_estimators=16, max_depth=5, random_state=0, backend=tpu_backend,
        hist_mode="scatter",
    ).fit(X, y)
    # same seeds -> identical forests regardless of backend
    np.testing.assert_allclose(
        local.predict_proba(X), dist.predict_proba(X), atol=1e-6
    )
    assert dist.backend is None
    pickle.dumps(dist)


def test_forest_partitions_rounds(clf_data):
    X, y = clf_data
    full = DistRandomForestClassifier(
        n_estimators=12, max_depth=5, random_state=0
    ).fit(X, y)
    rounds = DistRandomForestClassifier(
        n_estimators=12, max_depth=5, random_state=0, partitions=4
    ).fit(X, y)
    np.testing.assert_allclose(
        full.predict_proba(X), rounds.predict_proba(X), atol=1e-6
    )


def test_warm_start(clf_data):
    X, y = clf_data
    rf = DistRandomForestClassifier(
        n_estimators=10, max_depth=5, random_state=0, warm_start=True
    ).fit(X, y)
    rf.n_estimators = 20
    rf.fit(X, y)
    assert rf._trees["feat"].shape[0] == 20
    with pytest.raises(ValueError):
        rf.n_estimators = 5
        rf.fit(X, y)


def test_oob_score(clf_data, reg_data):
    """Real OOB scoring (the reference stubbed it, ensemble.py:338-340)."""
    X, y = clf_data
    rf = DistRandomForestClassifier(
        n_estimators=30, max_depth=5, random_state=0, oob_score=True
    ).fit(X, y)
    assert 0.7 <= rf.oob_score_ <= 1.0
    assert rf.oob_decision_function_.shape == (len(y), 3)
    # OOB is honest: no higher than train accuracy
    assert rf.oob_score_ <= rf.score(X, y) + 1e-9
    Xr, yr = reg_data
    rfr = DistRandomForestRegressor(
        n_estimators=30, max_depth=6, random_state=0, oob_score=True
    ).fit(Xr, yr)
    assert rfr.oob_prediction_.shape == (len(yr),)
    assert rfr.oob_score_ <= rfr.score(Xr, yr) + 1e-9
    with pytest.raises(ValueError):
        DistRandomForestClassifier(
            oob_score=True, bootstrap=False
        ).fit(X, y)


def test_oob_with_warm_start(clf_data):
    """OOB masks regenerate from stored seeds, so warm-started trees
    participate and nothing O(n) is persisted (regression)."""
    X, y = clf_data
    with pytest.warns(UserWarning, match="in-bag for every tree"):
        rf = DistRandomForestClassifier(
            n_estimators=10, max_depth=5, random_state=0, oob_score=True,
            warm_start=True,
        ).fit(X, y)
    first = rf.oob_score_
    rf.n_estimators = 20
    rf.fit(X, y)
    assert rf._trees["feat"].shape[0] == 20
    assert "oob_mask" not in rf._trees
    # more trees -> more OOB coverage; score stays sane
    assert 0.5 <= rf.oob_score_ <= 1.0
    assert abs(rf.oob_score_ - first) < 0.3


def test_forest_rejects_bad_class_weight(clf_data):
    X, y = clf_data
    with pytest.raises(ValueError):
        DistRandomForestClassifier(
            class_weight="balanced_subsample"
        ).fit(X, y)


def test_forest_class_weight(clf_data):
    X, y = clf_data
    keep = np.concatenate([np.where(y == 0)[0][:15], np.where(y != 0)[0]])
    Xi, yi = X[keep], y[keep]
    plain = DistRandomForestClassifier(
        n_estimators=20, max_depth=5, random_state=0
    ).fit(Xi, yi)
    bal = DistRandomForestClassifier(
        n_estimators=20, max_depth=5, random_state=0,
        class_weight="balanced",
    ).fit(Xi, yi)
    # balanced weighting should help the starved class's recall
    rec_plain = (plain.predict(Xi)[yi == 0] == 0).mean()
    rec_bal = (bal.predict(Xi)[yi == 0] == 0).mean()
    assert rec_bal >= rec_plain - 0.05


def test_warm_start_keeps_edges(clf_data):
    """Warm refit must not rebin old trees' thresholds (regression:
    edges were recomputed from the new X)."""
    X, y = clf_data
    rf = DistRandomForestClassifier(
        n_estimators=8, max_depth=5, random_state=0, warm_start=True
    ).fit(X, y)
    edges_before = rf._edges.copy()
    rf.n_estimators = 12
    rf.fit(X * 3.0 + 1.0, y)  # shifted distribution
    np.testing.assert_array_equal(rf._edges, edges_before)


def test_estimators_views(clf_data):
    X, y = clf_data
    rf = DistRandomForestClassifier(
        n_estimators=5, max_depth=5, random_state=0
    ).fit(X, y)
    assert len(rf.estimators_) == 5
    tree0 = rf.estimators_[0]
    p = tree0.predict_proba(X)
    assert p.shape == (len(y), 3)
    # forest proba is the mean of tree probas
    mean = np.mean([t.predict_proba(X) for t in rf.estimators_], axis=0)
    np.testing.assert_allclose(mean, rf.predict_proba(X), atol=1e-5)


def test_forest_apply_and_importances(clf_data):
    X, y = clf_data
    rf = DistRandomForestClassifier(
        n_estimators=6, max_depth=4, random_state=0
    ).fit(X, y)
    leaves = rf.apply(X)
    assert leaves.shape == (len(y), 6)
    imp = rf.feature_importances_
    assert imp.shape == (X.shape[1],)
    assert abs(imp.sum() - 1.0) < 1e-6


def test_get_oof_helpers(clf_data):
    """Module-level OOF helpers (reference ensemble.py:112-151)."""
    from skdist_tpu.distribute.ensemble import get_oof, get_single_oof

    X, y = clf_data
    clf = DistRandomForestClassifier(
        n_estimators=8, max_depth=4, random_state=0
    )
    fitted, oof = get_oof(clf, X, y, n_splits=3)
    assert oof.shape == (len(y), 3)
    assert np.allclose(oof.sum(axis=1), 1.0, atol=1e-5)
    # the helper's final fit is on the full data
    assert fitted.score(X, y) >= 0.9
    idx_test, proba = get_single_oof(
        DistRandomForestClassifier(n_estimators=6, max_depth=4,
                                   random_state=0),
        X, y, np.arange(0, 120), np.arange(120, 180),
    )
    assert proba.shape == (60, 3)


def test_forest_in_grid_search(clf_data):
    """Forests as search base estimators take the generic path."""
    from skdist_tpu.distribute.search import DistGridSearchCV

    X, y = clf_data
    gs = DistGridSearchCV(
        RandomForestClassifier(n_estimators=8, random_state=0),
        {"max_depth": [3, 5]}, cv=2, scoring="accuracy",
    ).fit(X, y)
    assert gs.best_params_["max_depth"] in (3, 5)


def test_dist_forest_classifier_byo_base(clf_data):
    """DistForestClassifier: the bring-your-own-tree intermediate
    (reference ensemble.py:343-363) — any sklearn-style base fans out
    one task per tree with bincount-bootstrap weights."""
    import pickle as pkl

    from sklearn.tree import DecisionTreeClassifier as SkDT

    X, y = clf_data
    f = DistForestClassifier(
        SkDT(max_depth=5), n_estimators=10, random_state=0
    ).fit(X, y)
    assert len(f.estimators_) == 10
    assert f.score(X, y) >= 0.95
    proba = f.predict_proba(X)
    assert proba.shape == (len(y), 3)
    np.testing.assert_allclose(proba.sum(axis=1), 1.0, atol=1e-8)
    # sklearn clone protocol works (get_params/set_params round trip)
    from sklearn.base import clone as sk_clone

    c = sk_clone(f)
    assert c.get_params()["base_estimator__max_depth"] == 5
    # picklable artifact
    loaded = pkl.loads(pkl.dumps(f))
    np.testing.assert_array_equal(loaded.predict(X), f.predict(X))


def test_dist_forest_regressor_byo_base(reg_data):
    from sklearn.tree import DecisionTreeRegressor as SkDTR

    X, y = reg_data
    f = DistForestRegressor(
        SkDTR(max_depth=6), n_estimators=10, random_state=0
    ).fit(X, y)
    assert f.score(X, y) > 0.5
    assert f.predict(X).shape == (len(y),)


def test_dist_forest_classifier_no_proba_base(clf_data):
    """Hard-vote fallback for bases without predict_proba."""
    from sklearn.svm import LinearSVC as SkSVC

    X, y = clf_data
    f = DistForestClassifier(
        SkSVC(max_iter=2000), n_estimators=5, random_state=0
    ).fit(X, y)
    assert f.score(X, y) >= 0.9
    proba = f.predict_proba(X)
    np.testing.assert_allclose(proba.sum(axis=1), 1.0, atol=1e-8)


def test_dist_forest_user_sample_weight(clf_data):
    """User sample_weight composes multiplicatively with the bootstrap
    bincount weights (review finding: it used to collide and crash)."""
    from sklearn.tree import DecisionTreeClassifier as SkDT

    X, y = clf_data
    w = np.where(y == 2, 0.0, 1.0)
    f = DistForestClassifier(
        SkDT(max_depth=5), n_estimators=8, random_state=0
    ).fit(X, y, sample_weight=w)
    preds = f.predict(X[y != 2])
    assert set(np.unique(preds)) <= {0, 1}
    # and with bootstrap disabled
    f2 = DistForestClassifier(
        SkDT(max_depth=5), n_estimators=4, random_state=0, bootstrap=False
    ).fit(X, y, sample_weight=w)
    assert set(np.unique(f2.predict(X[y != 2]))) <= {0, 1}


def test_dist_forest_partitions_and_set_params(clf_data):
    from sklearn.tree import DecisionTreeClassifier as SkDT

    X, y = clf_data
    a = DistForestClassifier(
        SkDT(max_depth=4), n_estimators=9, random_state=0
    ).fit(X, y)
    b = DistForestClassifier(
        SkDT(max_depth=4), n_estimators=9, random_state=0, partitions=3
    ).fit(X, y)
    # chunked rounds draw the same per-tree seeds -> identical forests
    np.testing.assert_allclose(a.predict_proba(X), b.predict_proba(X))
    # invalid params raise (BaseEstimator protocol, not silent attrs)
    with pytest.raises(ValueError, match="Invalid parameter"):
        a.set_params(n_estimatorz=5)
    a.set_params(base_estimator__max_depth=3)
    assert a.base_estimator.max_depth == 3


def test_hist_matmul_matches_scatter(clf_data, reg_data):
    """The MXU one-hot-matmul histogram must grow the same tree as the
    scatter histogram (same gains up to float-sum ordering)."""
    import jax
    import jax.numpy as jnp

    from skdist_tpu.models.tree import build_tree_kernel
    from skdist_tpu.models.forest import classification_channels
    from skdist_tpu.ops.binning import apply_bins, quantile_bin_edges

    X, y = clf_data
    edges = quantile_bin_edges(X, 16)
    Xb = apply_bins(jnp.asarray(X), edges)
    Ych = classification_channels(
        jnp.asarray(y), jnp.ones(len(y), jnp.float32), 3
    )
    cfg = dict(
        n_features=X.shape[1], n_bins=16, channels=4, max_depth=4,
        max_features=X.shape[1], min_samples_split=2, min_samples_leaf=1,
        min_impurity_decrease=0.0, extra=False, classification=True,
    )
    key = jax.random.PRNGKey(0)
    t_sc = build_tree_kernel(hist_mode="scatter", **cfg)(Xb, Ych, key)
    # matmul_sib (sibling subtraction) can flip near-tie splits in f32,
    # but on this well-separated fixture all three engines must agree
    for hm in ("matmul", "matmul_sib"):
        t_mm = build_tree_kernel(hist_mode=hm, **cfg)(Xb, Ych, key)
        np.testing.assert_array_equal(t_sc["feat"], t_mm["feat"], err_msg=hm)
        np.testing.assert_array_equal(t_sc["thr"], t_mm["thr"], err_msg=hm)
        np.testing.assert_array_equal(
            t_sc["is_split"], t_mm["is_split"], err_msg=hm
        )
        np.testing.assert_allclose(
            t_sc["leaf"], t_mm["leaf"], atol=1e-5, err_msg=hm
        )


def test_hist_mode_reaches_kernel_through_dist_wrappers(clf_data):
    """hist_mode plumbs from the Dist* constructors down to
    build_tree_kernel: both modes fit through the distributed wrapper
    and produce identical trees for identical seeds (the structural
    parity of test_hist_matmul_matches_scatter, end-to-end)."""
    X, y = clf_data
    preds = {}
    for hm in ("scatter", "matmul"):
        f = DistRandomForestClassifier(
            n_estimators=4, max_depth=4, random_state=7, hist_mode=hm,
        )
        assert f.get_params()["hist_mode"] == hm
        preds[hm] = f.fit(X, y).predict_proba(X)
    np.testing.assert_allclose(preds["scatter"], preds["matmul"], atol=1e-6)


@pytest.mark.parametrize("hist_mode,capped", [
    ("matmul", True), ("matmul_sib", True), ("scatter", False),
])
def test_matmul_forest_rounds_are_capped_by_operand_size(
        clf_data, tpu_backend, monkeypatch, hist_mode, capped):
    """The one-hot matmul engines materialise a (lanes, n, nl*C) right
    factor, and on the v5e a round whose factor passed ~2^32 elements
    returned wrong histograms without an error: forest.fit bounds the
    lanes per device to what 2^31 elements hold. Engines that
    materialise no such operand keep the caller's round."""
    from skdist_tpu.models import tree as tree_mod

    X, y = clf_data
    n = X.shape[0]
    # shrink the bound so THIS shape (depth 3, 4 channels) hits it at
    # two lanes a device
    monkeypatch.setattr(tree_mod, "MATMUL_MAX_OPERAND_ELEMS",
                        2 * n * 4 * 4 + 1)
    assert tree_mod.matmul_lane_cap(n, 3, 4) == 2
    assert tree_mod.matmul_lane_cap(10 ** 9, 8, 3) == 1  # never zero
    seen = {}
    real = type(tpu_backend).batched_map

    def spy(self, kernel, task_args, shared, round_size=None, **kw):
        seen["round_size"] = round_size
        return real(self, kernel, task_args, shared,
                    round_size=round_size, **kw)

    monkeypatch.setattr(type(tpu_backend), "batched_map", spy)
    n_trees = 6 * tpu_backend.n_task_slots
    DistRandomForestClassifier(
        n_estimators=n_trees, max_depth=3, random_state=0,
        hist_mode=hist_mode, backend=tpu_backend,
    ).fit(X, y)
    want = 2 * tpu_backend.n_task_slots if capped else n_trees
    assert seen["round_size"] == want


def test_hist_pallas_matches_scatter(clf_data):
    """hist_mode='pallas' (interpret mode on the CPU mesh) grows the
    identical tree to the scatter reference, including under vmap."""
    import jax
    import jax.numpy as jnp

    from skdist_tpu.models.tree import (
        build_tree_kernel,
        classification_channels,
    )
    from skdist_tpu.ops.binning import apply_bins, quantile_bin_edges

    X, y = clf_data
    edges = quantile_bin_edges(X, 16)
    Xb = apply_bins(jnp.asarray(X), jnp.asarray(edges))
    Ych = classification_channels(jnp.asarray(y), jnp.ones(len(y)), 3)
    cfg = dict(n_features=X.shape[1], n_bins=16, channels=4, max_depth=4,
               max_features=X.shape[1], min_samples_split=2,
               min_samples_leaf=1, min_impurity_decrease=0.0, extra=False,
               classification=True)
    key = jax.random.PRNGKey(3)
    t_sc = build_tree_kernel(hist_mode="scatter", **cfg)(Xb, Ych, key)
    t_pl = build_tree_kernel(hist_mode="pallas", **cfg)(Xb, Ych, key)
    np.testing.assert_array_equal(t_sc["feat"], t_pl["feat"])
    np.testing.assert_array_equal(t_sc["thr"], t_pl["thr"])
    np.testing.assert_array_equal(t_sc["is_split"], t_pl["is_split"])
    np.testing.assert_allclose(t_sc["leaf"], t_pl["leaf"], atol=1e-5)

    keys = jax.random.split(key, 3)
    trees = jax.vmap(
        lambda kk: build_tree_kernel(hist_mode="pallas", **cfg)(Xb, Ych, kk)
    )(keys)
    assert trees["feat"].shape == (3, 31)


def test_forest_bin_memo_engages_on_refit(clf_data, tpu_backend):
    """With reuse_broadcast, a second fit on the same host X must reuse
    the memoised binning (same Xb identity) and give identical trees;
    without it the memo must stay cold."""
    from skdist_tpu.distribute.ensemble import DistRandomForestClassifier
    from skdist_tpu.models import forest as forest_mod
    from skdist_tpu.parallel import TPUBackend

    X, y = clf_data
    forest_mod._EDGE_MEMO.clear()
    forest_mod._XB_MEMO.clear()
    kw = dict(n_estimators=4, max_depth=4, random_state=0)
    bk = TPUBackend(reuse_broadcast=True)
    f1 = DistRandomForestClassifier(backend=bk, **kw).fit(X, y)
    assert len(forest_mod._XB_MEMO) == 1
    key = next(iter(forest_mod._XB_MEMO))
    xb_first = forest_mod._XB_MEMO[key][2]
    assert xb_first is not None
    f2 = DistRandomForestClassifier(backend=bk, **kw).fit(X, y)
    assert forest_mod._XB_MEMO[key][2] is xb_first, \
        "refit on the same X must reuse the memoised Xb"
    np.testing.assert_array_equal(f1.predict(X), f2.predict(X))

    forest_mod._EDGE_MEMO.clear()
    forest_mod._XB_MEMO.clear()
    DistRandomForestClassifier(backend=tpu_backend, **kw).fit(X, y)
    assert len(forest_mod._XB_MEMO) == 0 \
        and len(forest_mod._EDGE_MEMO) == 0, \
        "memo must stay cold without reuse_broadcast"


def test_forest_bin_memo_warm_start_no_poisoning(tpu_backend):
    """Regression (round-2 advisor): a warm_start refit that APPLIES
    inherited edges to a new X must not poison the quantile-edge memo —
    a subsequent fresh fit on that same X must bin with X's own
    quantile edges, identically to an uncached fit."""
    from skdist_tpu.models import forest as forest_mod
    from skdist_tpu.models.forest import _memo_apply_bins, _memo_edges
    from skdist_tpu.models.tree import quantile_bin_edges

    rng = np.random.RandomState(7)
    X_old = rng.rand(80, 5).astype(np.float32) * 10.0
    X_new = rng.rand(80, 5).astype(np.float32)  # different scale
    n_bins = 8
    forest_mod._EDGE_MEMO.clear()
    forest_mod._XB_MEMO.clear()

    # warm-start shape of the bug: apply X_old's edges to X_new
    foreign_edges = np.asarray(quantile_bin_edges(X_old, n_bins))
    _memo_apply_bins(X_new, foreign_edges, n_bins, enabled=True)

    # a fresh fit asks for X_new's own quantile edges — must NOT get
    # the foreign (X_old-derived) edges back from the memo
    served = np.asarray(_memo_edges(X_new, n_bins, enabled=True))
    expected = np.asarray(quantile_bin_edges(X_new, n_bins))
    np.testing.assert_array_equal(served, expected)
    assert not np.array_equal(served, foreign_edges)
