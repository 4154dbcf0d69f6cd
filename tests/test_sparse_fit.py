"""Sparse-native fit data plane (skdist_tpu.sparse): packed-CSR shared
arrays, nnz-proportional solver kernels, routing, and the end-to-end
batched paths.

Covers the ISSUE-4 contract: dense-vs-packed parity fuzz for all four
linear families (weighted + fold-masked) over both packed
representations, the density routing (the bucketed representation's own
kernels: ``test_sparse_bucketed.py``), the operator's five contractions
per representation, pickle round-trip of a sparse-fit model, OvR/OvO
batched sparse grids, the no-recompile counters across rounds of mixed
representations, the chunked weighted gram and the bf16 contract.
"""

import inspect
import pickle

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from skdist_tpu import sparse as sx
from skdist_tpu.sparse import (
    BucketedX,
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


def _skewed_problem(seed=0, n=240, d=3000, k=3, heavy=(900, 600, 400)):
    """The same over a CSR that routes ``bucketed``: log-normal row
    lengths with a few rows in the hundreds, and Zipf column
    popularity, so that the densest columns make a dense head."""
    rng = np.random.RandomState(seed)
    lens = rng.lognormal(2.3, 1.0, n).astype(int)
    lens[:len(heavy)] = heavy
    lens = np.clip(lens, 1, d // 2)
    prob = 1.0 / (np.arange(d) + 5.0)
    prob /= prob.sum()
    cols = np.concatenate(
        [rng.choice(d, l, replace=False, p=prob) for l in lens])
    vals = (0.2 + rng.rand(len(cols))).astype(np.float32)
    X = sp.csr_matrix((vals, (np.repeat(np.arange(n), lens), cols)),
                      shape=(n, d))
    assert pack_decision(X)[:2] == (True, "bucketed")
    W = rng.normal(size=(d, k)).astype(np.float32)
    logits = np.asarray(X @ W)
    logits = (logits - logits.mean(0)) / (logits.std(0) + 1e-9)
    y = np.argmax(logits + 0.5 * rng.normal(size=(n, k)), axis=1)
    return X, y


#: the two packed representations, by the problem that routes to each
PACKED = {"padded": (_sparse_problem, PackedX),
          "bucketed": (_skewed_problem, BucketedX)}


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


@pytest.mark.parametrize("n,d,m,k", [
    (37, 53, 5, 3),       # nothing aligned to any tile
    (8, 300, 1, 1),       # single packed slot, single output
    (200, 1000, 17, 20),  # the multinomial shape class
    (5, 4, 4, 2),         # more slots a row than columns: duplicates
    (256, 512, 8, 4),     # tile-aligned
])
def test_padded_pair_kernels_match_dense_on_floats(n, d, m, k):
    """The padded pair's three kernels against the dense expressions on
    float data, at a pair with padded slots (``(0, 0.0)``), duplicate
    (row, column) entries (they add, as in a CSR) and the intercept
    column ``(idx=d, val=1)`` the operator appends."""
    rng = np.random.RandomState(n * 7 + k)
    idx = rng.randint(0, d, size=(n, m)).astype(np.int32)
    val = rng.randn(n, m).astype(np.float32)
    if m > 1:
        idx[:, 1] = idx[:, 0]
    pad = rng.rand(n, m) < 0.3
    idx[pad], val[pad] = 0, 0.0
    idx = np.concatenate([idx, np.full((n, 1), d, np.int32)], axis=1)
    val = np.concatenate([val, np.ones((n, 1), np.float32)], axis=1)
    Xa = np.zeros((n, d + 1), np.float64)
    np.add.at(Xa, (np.arange(n)[:, None], idx), val)
    assert np.all(Xa[:, d] == 1.0)
    np.testing.assert_allclose(packed_to_dense(idx, val, d + 1), Xa,
                               atol=1e-6)
    W = rng.randn(d + 1, k).astype(np.float32)
    r = rng.randn(n, k).astype(np.float32)
    sw = rng.rand(n).astype(np.float32)
    np.testing.assert_allclose(packed_matvec(idx, val, W), Xa @ W,
                               atol=2e-5)
    np.testing.assert_allclose(packed_matvec(idx, val, W[:, 0]),
                               Xa @ W[:, 0], atol=2e-5)
    np.testing.assert_allclose(packed_rmatvec(idx, val, r, d + 1),
                               Xa.T @ r, atol=2e-5)
    np.testing.assert_allclose(packed_rmatvec(idx, val, r[:, 0], d + 1),
                               Xa.T @ r[:, 0], atol=2e-5)
    G = np.asarray(packed_weighted_gram(idx, val, sw, d + 1))
    np.testing.assert_allclose(G, Xa.T @ (Xa * sw[:, None]), atol=5e-5)
    np.testing.assert_allclose(G, G.T, atol=1e-6)


@pytest.mark.parametrize("fit_intercept", [True, False],
                         ids=["intercept", "no_intercept"])
@pytest.mark.parametrize("representation", ["dense", "padded", "bucketed"])
def test_operator_contractions_equal_dense(representation, fit_intercept):
    """``LinearOperator(X, ...)`` is the implementation of X's type —
    no argument selects one — and each of the three holds the five
    contractions of ``[X | 1]`` (or of ``X``), vmapped gradient
    through ``matvec`` included."""
    assert list(inspect.signature(LinearOperator.__init__).parameters) == [
        "self", "X", "fit_intercept", "matmul_dtype"]
    if representation == "dense":
        Xd = np.random.RandomState(2).normal(size=(60, 9)).astype(np.float32)
        X = jnp.asarray(Xd)
    else:
        make, cls = PACKED[representation]
        Xs, _ = (make(seed=3, n=120, d=1024) if cls is PackedX
                 else make(seed=3, n=64, d=600, heavy=(250, 120)))
        Xd = np.asarray(Xs.toarray(), np.float32)
        X = jax.tree_util.tree_map(jnp.asarray, pack_for_fit(Xs))
        assert type(X) is cls
    n, d = Xd.shape
    op = LinearOperator(X, fit_intercept)
    other = LinearOperator(X, fit_intercept, matmul_dtype="bfloat16")
    assert type(op) is type(other) is not LinearOperator
    assert isinstance(op, LinearOperator) and other.bf16 and not op.bf16
    p = d + int(fit_intercept)
    assert (op.n, op.d, op.p, op.dtype) == (n, d, p, jnp.float32)
    Xa = np.hstack([Xd, np.ones((n, 1), np.float32)]) if fit_intercept \
        else Xd
    rng = np.random.RandomState(4)
    W = rng.randn(p, 3).astype(np.float32)
    r = rng.randn(n, 3).astype(np.float32)
    np.testing.assert_allclose(op.matvec(W), Xa @ W, atol=5e-5)
    np.testing.assert_allclose(op.matvec(W[:, 0]), Xa @ W[:, 0], atol=5e-5)
    np.testing.assert_allclose(op.rmatvec(r), Xa.T @ r, atol=5e-5)
    np.testing.assert_allclose(op.rmatvec(r[:, 0]), Xa.T @ r[:, 0],
                               atol=5e-5)
    rows = rng.randint(0, n, 16)
    g = rng.randn(16, 3).astype(np.float32)
    # jitted, as the fit problems run them (eagerly, a bucketed X
    # compiles every bucket's every op on its own)
    np.testing.assert_allclose(jax.jit(op.row_matvec)(rows, W),
                               Xa[rows] @ W, atol=5e-5)
    np.testing.assert_allclose(jax.jit(op.row_rmatvec)(rows, g),
                               Xa[rows].T @ g, atol=5e-5)
    sw = rng.rand(n).astype(np.float32)
    G, b = jax.jit(op.weighted_gram_rhs)(sw, r)
    np.testing.assert_allclose(G, Xa.T @ (Xa * sw[:, None]), atol=2e-4)
    np.testing.assert_allclose(b, (Xa * sw[:, None]).T @ r, atol=5e-5)

    def loss(Wl):
        return jnp.sum(jnp.tanh(op.matvec(Wl)) * r)

    lanes = rng.randn(3, p, 3).astype(np.float32)
    grads = jax.vmap(jax.grad(loss))(jnp.asarray(lanes))
    for Wl, got in zip(lanes, grads):
        want = Xa.T @ ((1.0 - np.tanh(Xa @ Wl) ** 2) * r)
        np.testing.assert_allclose(got, want, atol=1e-4)
    with pytest.raises(TypeError):
        LinearOperator(X, fit_intercept, None, "gather")


def test_packed_empty_rows_and_empty_matrix():
    X = sp.csr_matrix((5, 16), dtype=np.float32)
    idx, val = pack_csr_rows(X)
    assert idx.shape == (5, 1) and not val.any()
    np.testing.assert_array_equal(
        np.asarray(packed_matvec(idx, val, np.ones(16, np.float32))),
        np.zeros(5, np.float32))


@pytest.mark.parametrize("fit_intercept", [True, False])
def test_linear_operator_dense_matches_the_concatenated_copy(fit_intercept):
    """The dense operator carries the intercept BESIDE its products
    (no copy of X with a ones column exists in a program): all five
    contractions, and the classes-first ``logits``, against the plain
    expressions over the concatenated copy. Against a weight VECTOR the
    product is written lanes-first (PR 29), against a weight MATRIX
    ``logits`` is classes-first: a ``vmap`` over lanes lays them out
    ``(lanes, n)`` and ``(lanes, k, n)``, rows minor."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(5)
    n, d, k = 30, 7, 3
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    op = LinearOperator(X, fit_intercept=fit_intercept)
    Xa = (jnp.concatenate([X, jnp.ones((n, 1), X.dtype)], axis=1)
          if fit_intercept else X)
    p = d + int(fit_intercept)
    assert (op.p, op.class_axis) == (p, 0)
    close = dict(rtol=2e-6, atol=2e-6)
    w = jnp.asarray(rng.normal(size=p).astype(np.float32))
    W = jnp.asarray(rng.normal(size=(p, k)).astype(np.float32))
    np.testing.assert_allclose(op.matvec(w), Xa @ w, **close)
    np.testing.assert_allclose(op.matvec(W), Xa @ W, **close)
    np.testing.assert_allclose(op.logits(W), (Xa @ W).T, **close)
    for r in (rng.normal(size=n), rng.normal(size=(n, k))):
        r = jnp.asarray(r.astype(np.float32))
        np.testing.assert_allclose(op.rmatvec(r), Xa.T @ r, **close)
    i = jnp.asarray([4, 0, 29, 4])
    np.testing.assert_allclose(op.row_matvec(i, w), Xa[i] @ w, **close)
    np.testing.assert_allclose(op.row_matvec(i, W), Xa[i] @ W, **close)
    for g in (rng.normal(size=4), rng.normal(size=(4, k))):
        g = jnp.asarray(g.astype(np.float32))
        np.testing.assert_allclose(op.row_rmatvec(i, g), Xa[i].T @ g,
                                   **close)
    sw = jnp.asarray(rng.rand(n).astype(np.float32))
    for T in (rng.normal(size=(n, 2)), rng.normal(size=n)):
        T = jnp.asarray(T.astype(np.float32))
        G, b = op.weighted_gram_rhs(sw, T)
        Xw = Xa * sw[:, None]
        np.testing.assert_allclose(G, Xa.T @ Xw, **close)
        np.testing.assert_allclose(b, Xw.T @ T, **close)
    # the gradient a solver takes through the products is X̃ᵀ r
    r = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    np.testing.assert_allclose(
        jax.grad(lambda W: jnp.sum(op.logits(W) * r))(W), Xa.T @ r.T,
        **close)
    # under vmap the products come out rows-minor, no transpose after
    lanes = jnp.asarray(rng.normal(size=(4, p)).astype(np.float32))
    Lanes = jnp.asarray(rng.normal(size=(4, p, k)).astype(np.float32))
    for fn, arg, shape in ((op.matvec, lanes, (4, n)),
                           (op.logits, Lanes, (4, k, n))):
        eqns = jax.make_jaxpr(jax.vmap(fn))(arg).jaxpr.eqns
        dots = [e for e in eqns if e.primitive.name == "dot_general"]
        assert [e.outvars[0].aval.shape for e in dots] == [shape]
        after = eqns[eqns.index(dots[0]):]
        assert not any(e.primitive.name == "transpose" for e in after)
    # no value of a program is a second X
    for fn, arg in ((op.matvec, w), (op.logits, W), (op.rmatvec, sw)):
        assert not any(
            v.aval.shape in ((n, d + 1), (d + 1, n))
            for e in jax.make_jaxpr(fn)(arg).jaxpr.eqns for v in e.outvars)


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


@pytest.mark.parametrize("m, packs", [(16, True), (17, False)])
def test_pack_decision_boundary_is_four_times_the_bytes(m, packs):
    """Even rows of ``m`` elements over 128 columns: the pair packs
    exactly while ``n·m·8`` bytes stay a quarter of the dense ``n·d·4``
    (:data:`sparse.PACK_MIN_SAVINGS`, a constant: no switch moves it)."""
    n, d = 20, 128
    cols = np.stack([np.random.RandomState(i).choice(d, m, replace=False)
                     for i in range(n)])
    X = sp.csr_matrix((np.ones(n * m, np.float32),
                       (np.repeat(np.arange(n), m), cols.ravel())),
                      shape=(n, d))
    assert sx.PACK_MIN_SAVINGS == 4.0
    pack, reason, width = pack_decision(X)
    assert (pack, width) == (packs, m)
    assert reason == "packed" if packs else "dense-competitive" in reason
    assert (type(pack_for_fit(X)) is PackedX) == packs


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

@pytest.mark.parametrize("representation", sorted(PACKED))
@pytest.mark.parametrize("family", ["logreg", "svc", "sgd", "ridge"])
def test_family_parity_weighted_and_masked(family, representation,
                                           monkeypatch):
    """Each family's packed fit must match its dense fit to solver
    tolerance, including under per-sample weights composed with 0/1
    fold masks (the batched CV contract: masks are multiplicative
    weights, never row slicing) — over the padded pair and over a
    skewed CSR that packs bucketed (the full-batch products, the SGD
    row forms and the ridge gram of a ``BucketedX``)."""
    from skdist_tpu.base import clone
    from skdist_tpu.models import (
        LinearSVC,
        LogisticRegression,
        RidgeClassifier,
        SGDClassifier,
    )

    make, packed_cls = PACKED[representation]
    X, y = (make(seed=7, n=240, d=768, density=0.015)
            if representation == "padded" else make(seed=7, n=240, d=1536))
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
        # forcing ("1") packs the padded pair whatever the skew: the
        # bucketed leg leaves the routing to ``pack_decision``
        if not packed or representation == "padded":
            monkeypatch.setenv(SPARSE_FIT_ENV, "1" if packed else "0")
        try:
            return clone(est).fit(X, y, sample_weight=sw)
        finally:
            monkeypatch.delenv(SPARSE_FIT_ENV, raising=False)

    from skdist_tpu.models.linear import prepare_fit_X

    assert type(prepare_fit_X(X, est)) is packed_cls
    m_p, m_d = fit(True), fit(False)
    assert m_p._meta.get("x_format") == "packed"
    assert {k for k in m_p._meta if k.startswith("x_")} == {
        "x_format", "x_nnz", "x_slots"}
    assert m_p._meta["x_nnz"] >= X.nnz
    assert m_d._meta.get("x_format") is None
    tol = {"logreg": 5e-4, "svc": 5e-3, "sgd": 1e-5, "ridge": 1e-4}[family]
    np.testing.assert_allclose(m_p.coef_, m_d.coef_, atol=tol)
    Xh = np.asarray(X[:80].toarray(), np.float32)
    assert np.mean(m_p.predict(Xh) == m_d.predict(Xh)) >= 0.99


@pytest.mark.parametrize("representation", sorted(PACKED))
def test_ridge_regressor_sparse_parity(representation, monkeypatch):
    from skdist_tpu.models import Ridge

    X, _ = (_sparse_problem(seed=9, n=200, d=512, density=0.02)
            if representation == "padded"
            else _skewed_problem(seed=9, n=200, d=1024))
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

@pytest.mark.parametrize("representation", sorted(PACKED))
def test_sparse_fit_model_pickle_round_trip(representation):
    from skdist_tpu.models import LogisticRegression

    X, y = PACKED[representation][0](seed=13)
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


@pytest.mark.parametrize("representation", sorted(PACKED))
def test_sparse_predict_routes_packed(representation, monkeypatch):
    """decision_function on packable sparse input must not densify —
    the polymorphic kernel consumes the packed representation
    directly."""
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.models import linear as linear_mod

    X, y = PACKED[representation][0](seed=17)
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


@pytest.mark.parametrize("representation", sorted(PACKED))
def test_batch_predict_of_a_sparse_fit(representation):
    """A model fitted packed scores sparse rows (packed again at
    predict time) and the same rows dense alike, and ``batch_predict``
    streams the sparse rows to the same probabilities: the fitted
    artifact does not depend on the representation it was fitted on."""
    from skdist_tpu.distribute.predict import batch_predict
    from skdist_tpu.models import LogisticRegression

    X, y = PACKED[representation][0](seed=41, n=160)
    model = LogisticRegression(max_iter=40, engine="xla").fit(X, y)
    assert model._meta["x_format"] == "packed"
    Xh = np.asarray(X[:40].toarray(), np.float32)
    np.testing.assert_allclose(
        model.decision_function(X[:40]), model.decision_function(Xh),
        atol=1e-4)
    np.testing.assert_array_equal(model.predict(X[:40]), model.predict(Xh))
    out = batch_predict(model, X[:40], method="predict_proba")
    np.testing.assert_allclose(out, model.predict_proba(Xh), atol=1e-5)


# ---------------------------------------------------------------------------
# batched paths: CV grids, OvR/OvO, mixed-representation compile reuse
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("representation", ["dense", "padded", "bucketed"])
def test_round_stats_name_the_kernel_and_the_packing(representation,
                                                     tpu_backend):
    """``last_round_stats["kernel_mode"]`` reads ``"dense"`` for an
    ndarray search and ``"packed_gather"`` for both packed
    representations, which also book what was placed (``x_nnz``,
    ``x_slots``: ``packed_fill_pct.search`` reads them)."""
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression

    if representation == "dense":
        rng = np.random.RandomState(0)
        X = rng.normal(size=(90, 12)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int64)
    else:
        X, y = PACKED[representation][0](seed=21, n=150)
    DistGridSearchCV(
        LogisticRegression(max_iter=20, engine="xla"), {"C": [0.1, 1.0]},
        backend=tpu_backend, cv=3, scoring="accuracy", refit=False,
    ).fit(X, y)
    stats = tpu_backend.last_round_stats
    booked = {k for k in stats if k.startswith("x_")}
    if representation == "dense":
        assert stats["kernel_mode"] == "dense" and not booked
        return
    assert booked == {"x_nnz", "x_slots"}
    assert stats["kernel_mode"] == "packed_gather"
    twice = 2 if representation == "bucketed" else 1
    assert X.nnz <= stats["x_nnz"] <= twice * X.nnz < stats["x_slots"]


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


@pytest.mark.parametrize("representation", sorted(PACKED))
@pytest.mark.parametrize("which", ["ovr", "ovo"])
def test_multiclass_sparse_matches_dense(which, representation, tpu_backend,
                                         monkeypatch):
    from skdist_tpu.distribute.multiclass import (
        DistOneVsOneClassifier,
        DistOneVsRestClassifier,
    )
    from skdist_tpu.models import LinearSVC

    X, y = PACKED[representation][0](seed=29, n=300, d=768, k=4)
    cls = (DistOneVsRestClassifier if which == "ovr"
           else DistOneVsOneClassifier)
    est = LinearSVC(max_iter=120, tol=1e-6, engine="xla")

    m_p = cls(est, backend=tpu_backend).fit(X, y)
    if which == "ovr":
        # the OvR batched dispatch stamps the kernel it ran
        assert (tpu_backend.last_round_stats["kernel_mode"]
                == "packed_gather")
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


@pytest.mark.parametrize("order", [
    ("padded", "dense"), ("dense", "bucketed", "padded")],
    ids=["padded-dense", "dense-bucketed-padded"])
def test_no_recompile_across_mixed_sparse_dense_rounds(order, tpu_backend):
    """Repeated grids over one representation reuse ONE compiled
    program each — the dense matrix's, the padded pair's, the bucketed
    matrix's: each compiles a program of its own when first seen (the
    structural keys tell dense from packed, the treedef a padded pair
    from a bucketed matrix), and interleaving them never compiles
    again."""
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.parallel import compile_cache

    # a row count of its own for each case: the other's programs, left
    # in this process's caches, are none of this one's
    n = 192 + 8 * len(order)
    X, y = _sparse_problem(seed=31, n=n, d=640)
    data = {"padded": X, "dense": np.asarray(X.toarray(), np.float32),
            "bucketed": _skewed_problem(seed=31, n=n, d=640)[0]}
    grid = {"C": [0.1, 1.0]}

    def run(which):
        return DistGridSearchCV(
            LogisticRegression(max_iter=40, engine="xla"), grid,
            backend=tpu_backend, cv=3, scoring="accuracy", refit=False,
        ).fit(data[which], y)

    cold = []
    for which in order:
        before = compile_cache.snapshot()["aot_misses"]
        run(which)
        cold.append(compile_cache.snapshot()["aot_misses"] - before)
    assert all(c > 0 for c in cold), cold
    snap = compile_cache.snapshot()
    for which in order + order[::-1]:
        run(which)
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


# ---------------------------------------------------------------------------
# the chunked weighted gram
# ---------------------------------------------------------------------------

def test_weighted_gram_chunked_matches_unchunked():
    rng = np.random.RandomState(7)
    n, d, m = 100, 64, 5
    idx = rng.randint(0, d, size=(n, m)).astype(np.int32)
    val = rng.randn(n, m).astype(np.float32)
    sw = rng.rand(n).astype(np.float32)
    full = np.asarray(sx.packed_weighted_gram(
        jnp.asarray(idx), jnp.asarray(val), jnp.asarray(sw), d,
        row_chunk=None))
    for chunk in (1, 7, 32, 100, 1000):
        out = np.asarray(sx.packed_weighted_gram(
            jnp.asarray(idx), jnp.asarray(val), jnp.asarray(sw), d,
            row_chunk=chunk))
        np.testing.assert_allclose(out, full, atol=1e-5)
    # integer data: bitwise across every chunking (f32-exact sums)
    vi = rng.randint(-3, 4, size=(n, m)).astype(np.float32)
    si = rng.randint(0, 3, size=n).astype(np.float32)
    fi = np.asarray(sx.packed_weighted_gram(
        jnp.asarray(idx), jnp.asarray(vi), jnp.asarray(si), d,
        row_chunk=n))
    ci = np.asarray(sx.packed_weighted_gram(
        jnp.asarray(idx), jnp.asarray(vi), jnp.asarray(si), d,
        row_chunk=9))
    np.testing.assert_array_equal(ci, fi)


def test_weighted_gram_env_chunk_and_budget(monkeypatch):
    """The env override engages chunking, and the budget plumbing
    chunks automatically when the (n, m, m) tensor overshoots its
    share — the ridge family's guard against the unguarded
    materialisation."""
    rng = np.random.RandomState(3)
    n, d, m = 64, 48, 4
    idx = rng.randint(0, d, size=(n, m)).astype(np.int32)
    val = rng.randn(n, m).astype(np.float32)
    sw = rng.rand(n).astype(np.float32)
    ref = np.asarray(sx.packed_weighted_gram(
        jnp.asarray(idx), jnp.asarray(val), jnp.asarray(sw), d,
        row_chunk=n))
    monkeypatch.setenv(sx.GRAM_CHUNK_ENV, "5")
    assert sx._gram_row_chunk(n, m) == 5
    out = np.asarray(sx.packed_weighted_gram(
        jnp.asarray(idx), jnp.asarray(val), jnp.asarray(sw), d))
    np.testing.assert_allclose(out, ref, atol=1e-5)
    monkeypatch.delenv(sx.GRAM_CHUNK_ENV)
    # a budget far below the contribution tensor forces a small chunk
    from skdist_tpu.utils.meminfo import BUDGET_ENV

    monkeypatch.setenv(BUDGET_ENV, str(n * m * m * 4 // 2))
    chunk = sx._gram_row_chunk(n, m)
    assert chunk is not None and 1 <= chunk < n
    monkeypatch.delenv(BUDGET_ENV)


def test_ridge_fit_with_forced_gram_chunk(monkeypatch):
    """A ridge fit (the gram consumer) under a forced tiny chunk lands
    on the dense path's coefficients. Order matters: the env must be
    set BEFORE this shape's packed fit kernel first traces (trace-time
    decision, memoised kernel), and the reference comes from the
    dense-forced path — a different program family — so the chunked
    gram is genuinely the one under test."""
    from skdist_tpu.models import Ridge

    X, _ = _sparse_problem(seed=5, n=151, d=257, density=0.02)
    rng = np.random.RandomState(2)
    yr = np.asarray(
        X @ rng.normal(size=X.shape[1]).astype(np.float32)
    ) + 0.05 * rng.normal(size=X.shape[0]).astype(np.float32)
    monkeypatch.setenv(sx.GRAM_CHUNK_ENV, "17")
    m_chunk = Ridge(alpha=1.0).fit(X, yr)
    monkeypatch.delenv(sx.GRAM_CHUNK_ENV)
    assert m_chunk._meta.get("x_format") == "packed"
    monkeypatch.setenv(sx.SPARSE_FIT_ENV, "0")
    m_dense = Ridge(alpha=1.0).fit(X, yr)
    monkeypatch.delenv(sx.SPARSE_FIT_ENV)
    np.testing.assert_allclose(m_chunk.coef_, m_dense.coef_, atol=1e-3)


# ---------------------------------------------------------------------------
# the bf16 matmul_dtype contract on the padded pair
# ---------------------------------------------------------------------------

def test_bf16_contract_on_packed_gather():
    """sparse.py documents the packed bf16 pass as round-to-bf16
    products before the f32 row-sum: pin that exact numerics contract
    (reference emulation, bitwise) and its agreement class with the
    dense bf16 pass."""
    rng = np.random.RandomState(13)
    n, d, m, k = 80, 96, 6, 3
    X = sp.random(n, d, density=m / d, format="csr",
                  dtype=np.float32, random_state=rng)
    packed = sx.pack_for_fit(X)
    if packed is None:  # density heuristics: force-pack for the test
        idx, val = sx.pack_csr_rows(X)
        packed = sx.PackedX(idx, val, d)
    W = jnp.asarray(rng.randn(d + 1, k).astype(np.float32))
    op = sx.LinearOperator(packed, fit_intercept=True,
                           matmul_dtype="bfloat16")
    out = np.asarray(op.matvec(W))
    # reference emulation of the documented contract
    g = W.astype(jnp.bfloat16)[op.pidx]
    v = op.pval.astype(jnp.bfloat16)
    ref = np.asarray(jnp.sum(
        (v[:, :, None] * g).astype(jnp.float32), axis=1))
    np.testing.assert_array_equal(out, ref)
    # agreement with the dense bf16 pass: same precision class (bf16
    # has ~3 significant decimal digits; magnitudes here are O(1-10))
    Xd = jnp.asarray(np.asarray(X.toarray(), np.float32))
    op_d = sx.LinearOperator(Xd, fit_intercept=True,
                             matmul_dtype="bfloat16")
    dense = np.asarray(op_d.matvec(W))
    f32 = np.asarray(sx.LinearOperator(
        Xd, fit_intercept=True).matvec(W))
    scale = np.maximum(1.0, np.abs(f32))
    assert np.max(np.abs(out - dense) / scale) < 0.02
    assert np.max(np.abs(out - f32) / scale) < 0.02
