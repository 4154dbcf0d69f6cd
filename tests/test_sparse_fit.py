"""Sparse-native fit data plane (skdist_tpu.sparse): packed-CSR shared
arrays, nnz-proportional solver kernels, routing, and the end-to-end
batched paths.

Covers the ISSUE-4 contract: dense-vs-packed parity fuzz for all four
linear families (weighted + fold-masked), the density routing (skewed
row lengths pack bucketed: ``test_sparse_bucketed.py``), pickle round-trip of a sparse-fit model,
OvR/OvO batched sparse grids, and the no-recompile counters across
mixed sparse/dense rounds.
"""

import pickle

import numpy as np
import pytest
import scipy.sparse as sp

from skdist_tpu.sparse import (
    PackedX,
    SPARSE_FIT_ENV,
    LinearOperator,
    pack_csr_rows,
    pack_decision,
    pack_for_fit,
    packed_matvec,
    packed_rmatvec,
    packed_to_dense,
    packed_weighted_gram,
)


def _sparse_problem(seed=0, n=300, d=1024, density=0.01, k=3):
    rng = np.random.RandomState(seed)
    X = sp.random(n, d, density=density, format="csr",
                  dtype=np.float32, random_state=rng)
    W = rng.normal(size=(d, k)).astype(np.float32)
    logits = np.asarray(X @ W)
    logits = (logits - logits.mean(0)) / (logits.std(0) + 1e-9)
    y = np.argmax(logits + 0.5 * rng.normal(size=(n, k)), axis=1)
    return X, y


# ---------------------------------------------------------------------------
# packing + kernels
# ---------------------------------------------------------------------------

def test_packed_kernels_match_dense_bitwise_on_integers():
    """Integer-valued inputs: f32 sums below 2^24 are exact regardless
    of reduction order, so gather/scatter must be BITWISE identical to
    the dense contractions (the engine_fuzz leg's unit-tier twin)."""
    rng = np.random.RandomState(3)
    n, d, k = 67, 40, 3
    X = sp.random(n, d, density=0.15, format="csr", random_state=rng,
                  data_rvs=lambda s: rng.randint(1, 6, size=s))
    X = X.astype(np.float32)
    Xd = np.asarray(X.toarray(), np.float32)
    idx, val = pack_csr_rows(X)
    W = rng.randint(-4, 5, size=(d, k)).astype(np.float32)
    r = rng.randint(-4, 5, size=(n, k)).astype(np.float32)
    sw = rng.randint(0, 3, size=n).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(packed_matvec(idx, val, W[:, 0])), Xd @ W[:, 0])
    np.testing.assert_array_equal(
        np.asarray(packed_matvec(idx, val, W)), Xd @ W)
    np.testing.assert_array_equal(
        np.asarray(packed_rmatvec(idx, val, r[:, 0], d)), Xd.T @ r[:, 0])
    np.testing.assert_array_equal(
        np.asarray(packed_rmatvec(idx, val, r, d)), Xd.T @ r)
    np.testing.assert_array_equal(
        np.asarray(packed_to_dense(idx, val, d)), Xd)
    np.testing.assert_array_equal(
        np.asarray(packed_weighted_gram(idx, val, sw, d)),
        Xd.T @ (Xd * sw[:, None]))


def test_packed_empty_rows_and_empty_matrix():
    X = sp.csr_matrix((5, 16), dtype=np.float32)
    idx, val = pack_csr_rows(X)
    assert idx.shape == (5, 1) and not val.any()
    np.testing.assert_array_equal(
        np.asarray(packed_matvec(idx, val, np.ones(16, np.float32))),
        np.zeros(5, np.float32))


def test_linear_operator_dense_matches_legacy_expressions():
    """The dense branch must reproduce the historical ops verbatim —
    the dense paths' pinned numerics depend on it. The one that moved
    (PR 29): against a weight VECTOR the product is written lanes-first,
    ``w @ Xa.T`` — the same contraction, whose logits a ``vmap`` over
    lanes lays out ``(lanes, n)`` instead of ``(n, lanes)``."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(5)
    X = jnp.asarray(rng.normal(size=(30, 7)).astype(np.float32))
    op = LinearOperator(X, fit_intercept=True)
    Xa = jnp.concatenate([X, jnp.ones((30, 1), X.dtype)], axis=1)
    w = jnp.asarray(rng.normal(size=8).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(op.matvec(w)),
                                  np.asarray(w @ Xa.T))
    np.testing.assert_allclose(np.asarray(op.matvec(w)),
                               np.asarray(Xa @ w), rtol=1e-6, atol=1e-6)
    lanes = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
    assert jax.vmap(op.matvec)(lanes).shape == (4, 30)
    assert jax.make_jaxpr(jax.vmap(op.matvec))(lanes).jaxpr.eqns[-1] \
        .primitive.name == "dot_general"  # no transpose after the product
    W = jnp.asarray(rng.normal(size=(8, 3)).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(op.matvec(W)),
                                  np.asarray(Xa @ W))
    sw = jnp.asarray(rng.rand(30).astype(np.float32))
    T = jnp.asarray(rng.normal(size=(30, 2)).astype(np.float32))
    G, b = op.weighted_gram_rhs(sw, T)
    Xw = Xa * sw[:, None]
    np.testing.assert_array_equal(np.asarray(G), np.asarray(Xa.T @ Xw))
    np.testing.assert_array_equal(np.asarray(b), np.asarray(Xw.T @ T))


# ---------------------------------------------------------------------------
# routing: pack decision, env switches
# ---------------------------------------------------------------------------

def test_pack_decision_density_and_overrides(monkeypatch):
    rng = np.random.RandomState(0)
    sparse = sp.random(100, 1024, density=0.01, format="csr",
                       dtype=np.float32, random_state=rng)
    dense_ish = sp.random(100, 64, density=0.5, format="csr",
                          dtype=np.float32, random_state=rng)
    assert pack_decision(sparse)[0]
    assert not pack_decision(dense_ish)[0]
    # env kill switch / force switch
    monkeypatch.setenv(SPARSE_FIT_ENV, "0")
    assert not pack_decision(sparse)[0]
    monkeypatch.setenv(SPARSE_FIT_ENV, "1")
    assert pack_decision(dense_ish)[0]
    monkeypatch.delenv(SPARSE_FIT_ENV)
    # non-sparse / 1-D sparse inputs never pack
    assert pack_for_fit(np.zeros((10, 4), np.float32)) is None
    try:
        v = sp.csr_array(np.arange(5, dtype=np.float64))
    except (TypeError, ValueError):
        v = None
    if v is not None and len(v.shape) == 1:
        assert pack_for_fit(v) is None


def test_explicit_host_pin_beats_packing():
    """engine='host' is an explicit pin: it densifies (the f64 BLAS
    engine has no packed form) instead of silently rerouting to the
    packed XLA path; engine='auto' packs."""
    from skdist_tpu.models import LogisticRegression

    X, y = _sparse_problem(seed=41, n=150, d=512)
    pinned = LogisticRegression(max_iter=40, engine="host").fit(X, y)
    assert pinned._meta.get("x_format") is None
    auto = LogisticRegression(max_iter=40).fit(X, y)
    assert auto._meta.get("x_format") == "packed"


def test_prepare_fit_x_respects_family_support():
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.models.linear import prepare_fit_X
    from skdist_tpu.models.tree import DecisionTreeClassifier

    X, _ = _sparse_problem()
    assert isinstance(prepare_fit_X(X, LogisticRegression), PackedX)
    # families without the packed contract (trees) stay dense
    assert isinstance(
        prepare_fit_X(X, DecisionTreeClassifier), np.ndarray
    )


# ---------------------------------------------------------------------------
# dense-vs-packed parity fuzz: all four families, weighted + fold-masked
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["logreg", "svc", "sgd", "ridge"])
def test_family_parity_weighted_and_masked(family, monkeypatch):
    """Each family's packed fit must match its dense fit to solver
    tolerance, including under per-sample weights composed with 0/1
    fold masks (the batched CV contract: masks are multiplicative
    weights, never row slicing)."""
    from skdist_tpu.base import clone
    from skdist_tpu.models import (
        LinearSVC,
        LogisticRegression,
        RidgeClassifier,
        SGDClassifier,
    )

    X, y = _sparse_problem(seed=7, n=240, d=768, density=0.015)
    rng = np.random.RandomState(11)
    # user weights x fold mask (a third of the rows zeroed)
    sw = (0.5 + rng.rand(X.shape[0])).astype(np.float32)
    sw[rng.choice(X.shape[0], size=X.shape[0] // 3, replace=False)] = 0.0

    est = {
        "logreg": LogisticRegression(C=0.1, tol=1e-7, max_iter=400,
                                     engine="xla"),
        "svc": LinearSVC(C=0.1, tol=1e-7, max_iter=400, engine="xla"),
        "sgd": SGDClassifier(loss="log_loss", max_iter=8, random_state=3),
        "ridge": RidgeClassifier(alpha=1.0),
    }[family]

    def fit(packed):
        monkeypatch.setenv(SPARSE_FIT_ENV, "1" if packed else "0")
        try:
            return clone(est).fit(X, y, sample_weight=sw)
        finally:
            monkeypatch.delenv(SPARSE_FIT_ENV)

    m_p, m_d = fit(True), fit(False)
    assert m_p._meta.get("x_format") == "packed"
    assert m_d._meta.get("x_format") is None
    tol = {"logreg": 5e-4, "svc": 5e-3, "sgd": 1e-5, "ridge": 1e-4}[family]
    np.testing.assert_allclose(m_p.coef_, m_d.coef_, atol=tol)
    Xh = np.asarray(X[:80].toarray(), np.float32)
    assert np.mean(m_p.predict(Xh) == m_d.predict(Xh)) >= 0.99


def test_ridge_regressor_sparse_parity(monkeypatch):
    from skdist_tpu.models import Ridge

    X, _ = _sparse_problem(seed=9, n=200, d=512, density=0.02)
    rng = np.random.RandomState(2)
    yr = np.asarray(X @ rng.normal(size=X.shape[1]).astype(np.float32))
    yr += 0.05 * rng.normal(size=len(yr)).astype(np.float32)
    sw = (0.5 + rng.rand(len(yr))).astype(np.float32)

    m_p = Ridge(alpha=2.0).fit(X, yr, sample_weight=sw)
    monkeypatch.setenv(SPARSE_FIT_ENV, "0")
    m_d = Ridge(alpha=2.0).fit(X, yr, sample_weight=sw)
    monkeypatch.delenv(SPARSE_FIT_ENV)
    assert isinstance(m_p._meta.get("x_format"), str)
    np.testing.assert_allclose(m_p.coef_, m_d.coef_, atol=1e-3)
    np.testing.assert_allclose(
        m_p.predict(np.asarray(X[:40].toarray(), np.float32)),
        m_d.predict(np.asarray(X[:40].toarray(), np.float32)),
        atol=1e-3,
    )


# ---------------------------------------------------------------------------
# fitted artifacts: pickle, predict-side routing
# ---------------------------------------------------------------------------

def test_sparse_fit_model_pickle_round_trip():
    from skdist_tpu.models import LogisticRegression

    X, y = _sparse_problem(seed=13)
    model = LogisticRegression(max_iter=100, engine="xla").fit(X, y)
    assert model._meta["x_format"] == "packed"
    blob = pickle.dumps(model)
    back = pickle.loads(blob)
    Xh = np.asarray(X[:50].toarray(), np.float32)
    np.testing.assert_array_equal(back.predict(Xh), model.predict(Xh))
    # the revived model still scores SPARSE input through the packed
    # polymorphic decision kernel (no densification)
    np.testing.assert_allclose(
        back.predict_proba(X[:50]), model.predict_proba(Xh), atol=1e-6
    )


def test_sparse_predict_routes_packed(monkeypatch):
    """decision_function on packable sparse input must not densify —
    the polymorphic kernel consumes the packed pair directly."""
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.models import linear as linear_mod

    X, y = _sparse_problem(seed=17)
    model = LogisticRegression(max_iter=60, engine="xla").fit(X, y)

    calls = []
    real = linear_mod.as_dense_f32

    def spy(A):
        calls.append(np.shape(A))
        return real(A)

    monkeypatch.setattr(linear_mod, "as_dense_f32", spy)
    scores_sparse = model.decision_function(X)
    assert calls == []  # never densified
    scores_dense = model.decision_function(
        np.asarray(X.toarray(), np.float32)
    )
    np.testing.assert_allclose(scores_sparse, scores_dense, atol=1e-4)


# ---------------------------------------------------------------------------
# batched paths: CV grids, OvR/OvO, mixed-representation compile reuse
# ---------------------------------------------------------------------------

def test_grid_search_sparse_matches_dense(tpu_backend, monkeypatch):
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression

    X, y = _sparse_problem(seed=21, n=360, d=1024)
    grid = {"C": [0.05, 0.5, 5.0]}
    est = LogisticRegression(max_iter=80, engine="xla")

    gs_p = DistGridSearchCV(est, grid, backend=tpu_backend, cv=3,
                            scoring="accuracy", refit=False).fit(X, y)
    assert tpu_backend.last_shared_bytes is not None
    packed_bytes = tpu_backend.last_shared_bytes
    monkeypatch.setenv(SPARSE_FIT_ENV, "0")
    gs_d = DistGridSearchCV(est, grid, backend=tpu_backend, cv=3,
                            scoring="accuracy", refit=False).fit(X, y)
    monkeypatch.delenv(SPARSE_FIT_ENV)
    dense_bytes = tpu_backend.last_shared_bytes
    np.testing.assert_allclose(
        np.asarray(gs_p.cv_results_["mean_test_score"]),
        np.asarray(gs_d.cv_results_["mean_test_score"]),
        atol=1e-5,
    )
    # the placement layer byte-accounts the packed pair at its true
    # size: the shared tree must be several times smaller
    assert packed_bytes * 4 < dense_bytes


def test_grid_search_sparse_weighted(tpu_backend):
    """Full-length sample_weight rides the batched sparse path (the
    fold masks compose multiplicatively, same as dense)."""
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression

    X, y = _sparse_problem(seed=23, n=240, d=768)
    rng = np.random.RandomState(5)
    sw = (0.2 + rng.rand(X.shape[0])).astype(np.float32)
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=60, engine="xla"),
        {"C": [0.1, 1.0]}, backend=tpu_backend, cv=3,
        scoring="accuracy", refit=False,
    ).fit(X, y, sample_weight=sw)
    assert np.isfinite(
        np.asarray(gs.cv_results_["mean_test_score"])
    ).all()


@pytest.mark.parametrize("which", ["ovr", "ovo"])
def test_multiclass_sparse_matches_dense(which, tpu_backend, monkeypatch):
    from skdist_tpu.distribute.multiclass import (
        DistOneVsOneClassifier,
        DistOneVsRestClassifier,
    )
    from skdist_tpu.models import LinearSVC

    X, y = _sparse_problem(seed=29, n=300, d=768, k=4)
    cls = (DistOneVsRestClassifier if which == "ovr"
           else DistOneVsOneClassifier)
    est = LinearSVC(max_iter=120, tol=1e-6, engine="xla")

    m_p = cls(est, backend=tpu_backend).fit(X, y)
    monkeypatch.setenv(SPARSE_FIT_ENV, "0")
    m_d = cls(est, backend=tpu_backend).fit(X, y)
    monkeypatch.delenv(SPARSE_FIT_ENV)
    Xh = np.asarray(X[:100].toarray(), np.float32)
    assert np.mean(m_p.predict(Xh) == m_d.predict(Xh)) >= 0.98
    # per-class artifacts carry the packed meta and still predict dense
    jax_ests = [e for e in m_p.estimators_ if hasattr(e, "_meta")]
    assert jax_ests and all(
        e._meta.get("x_format") == "packed" for e in jax_ests
    )


def test_no_recompile_across_mixed_sparse_dense_rounds(tpu_backend,
                                                       monkeypatch):
    """Structural keys carry the representation: repeated sparse grids
    reuse ONE compiled program, repeated dense grids another, and
    interleaving them never cross-compiles."""
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.parallel import compile_cache

    X, y = _sparse_problem(seed=31, n=200, d=640)
    Xd = np.asarray(X.toarray(), np.float32)
    grid = {"C": [0.1, 1.0]}

    def run(data):
        return DistGridSearchCV(
            LogisticRegression(max_iter=40, engine="xla"), grid,
            backend=tpu_backend, cv=3, scoring="accuracy", refit=False,
        ).fit(data, y)

    run(X)   # cold sparse
    run(Xd)  # cold dense
    snap = compile_cache.snapshot()
    run(X)
    run(Xd)
    run(X)
    after = compile_cache.snapshot()
    assert after["jit_misses"] == snap["jit_misses"]
    assert after["aot_misses"] == snap["aot_misses"]
    assert after["kernel_misses"] == snap["kernel_misses"]


def test_packed_x_through_backend_placement(tpu_backend):
    """PackedX is a registered pytree: backend placement, sharding and
    gather treat its two leaves like any other shared arrays."""
    import jax.numpy as jnp

    X, _ = _sparse_problem(seed=37, n=64, d=256)
    packed = pack_for_fit(X)
    assert isinstance(packed, PackedX)

    def kernel(shared, task):
        return {"s": packed_matvec(
            shared["X"].idx, shared["X"].val,
            jnp.ones(shared["X"].n_cols, jnp.float32),
        ).sum() * task["a"]}

    out = tpu_backend.batched_map(
        kernel, {"a": np.ones(8, np.float32)}, {"X": packed}
    )
    expected = float(np.asarray(X.sum()))
    np.testing.assert_allclose(out["s"], expected, rtol=1e-5)
    assert tpu_backend.last_shared_bytes == packed.nbytes
