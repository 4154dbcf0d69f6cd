"""
The bucketed packed representation (``sparse.BucketedX``): a CSR whose
row lengths are heavy-tailed packs at a cost that follows nnz, its
products equal the dense ones whatever its structure (no head, a head
alone, one bucket, empty rows), its dense head is built on the device
from the stored elements, ``toarray``'s to the bit and with no dense
copy on the host, a matrix of even rows still packs to
the one padded pair it always did, and a grid search
over a skewed 20-class CSR runs packed end to end and agrees with the
benchmark's plain reference for sparse inputs.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from skdist_tpu import sparse as sx


def skewed_csr(seed=0, n=600, d=5000, heavy=(2000, 1500, 900)):
    """Log-normal row lengths with a few rows in the thousands."""
    rng = np.random.RandomState(seed)
    lens = np.clip(rng.lognormal(3.0, 1.0, n).astype(int), 1, d // 2)
    lens[:len(heavy)] = heavy
    rows = np.repeat(np.arange(n), lens)
    cols = np.concatenate([rng.choice(d, l, replace=False) for l in lens])
    vals = rng.rand(len(rows)).astype(np.float32)
    return sp.csr_matrix((vals, (rows, cols)), shape=(n, d))


def _one_element_columns(lens, seed=5):
    """Rows of the given lengths over columns that hold one element
    each at most: no column is dense enough for the head."""
    rng = np.random.RandomState(seed)
    total = int(np.sum(lens))
    return sp.csr_matrix(
        (0.5 + rng.rand(total).astype(np.float32),
         (np.repeat(np.arange(len(lens)), lens),
          rng.permutation(2 * total)[:total])),
        shape=(len(lens), 2 * total))


def _structured(structure):
    """``(CSR, what its BucketedX must look like)``."""
    rng = np.random.RandomState(7)
    if structure == "skewed":
        def check(B, X):
            # the densest columns are held dense, the rest packed at a
            # cost that follows nnz: far under max-row padding
            n = X.shape[0]
            assert len(B.rows) > 3
            assert B.head.shape == (n, B.head_cols.shape[0])
            assert 0 < B.head_nnz < B.nnz
            assert B.slots - B.head.size < 0.25 * n * np.diff(X.indptr).max()
        X = skewed_csr()
        assert sx.pack_decision(X)[:2] == (True, "bucketed")
        return X, check
    if structure == "no_head":
        def check(B, X):
            assert B.head is None and B.head_cols is None
            assert B.head_nnz == 0 and len(B.rows) > 2
        return _one_element_columns(
            np.r_[300, 120, rng.randint(1, 12, 58)]), check
    if structure == "head_only":
        # every stored element in an eighth of the columns, each dense
        # enough: the buckets of both orientations hold padding alone
        def check(B, X):
            assert B.head_nnz == B.nnz == X.nnz
            assert B.head.shape[1] == 64
            assert not any(np.asarray(v).any() for _, v in B.rows + B.cols)
        dense = sp.random(48, 64, density=0.5, format="csr",
                          dtype=np.float32, random_state=rng)
        return sp.hstack([sp.csr_matrix((48, 200), dtype=np.float32),
                          dense,
                          sp.csr_matrix((48, 248), dtype=np.float32)]
                         ).tocsr(), check
    if structure == "one_bucket":
        def check(B, X):
            assert B.head is None
            assert len(B.rows) == len(B.cols) == 1
            assert B.rows[0][0].shape[2] == 8
        return _one_element_columns(np.full(40, 8)), check
    assert structure == "empty_rows_and_column"

    def check(B, X):
        assert B.head is not None and B.nnz == X.nnz
    X = skewed_csr(seed=2, n=120, d=1500, heavy=(500, 300))
    keep = sp.diags(np.r_[1, 1, 1, np.zeros(6), np.ones(110), 0.0])
    X = (keep @ X @ sp.diags(np.r_[0.0, np.ones(1498), 0.0])).tocsr()
    X = X.astype(np.float32)
    X.eliminate_zeros()
    assert (np.diff(X.indptr) == 0).sum() >= 7
    return X, check


@pytest.mark.parametrize("structure", [
    "skewed", "no_head", "head_only", "one_bucket",
    "empty_rows_and_column"])
def test_bucketed_products_equal_dense(structure):
    """matvec, rmatvec, the row forms and the vmapped value-and-gradient
    through the operator, against the dense matrix — for a ``BucketedX``
    of every make-up ``pack_csr_buckets`` can answer."""
    X, check = _structured(structure)
    n, d = X.shape
    k = 5
    B = jax.tree_util.tree_map(jnp.asarray, sx.pack_csr_buckets(X))
    assert isinstance(B, sx.BucketedX) and B.shape == (n, d)
    assert B.nnz == X.nnz
    assert B.placed == 2 * (B.nnz - B.head_nnz) + B.head_nnz
    check(B, X)
    Xd = X.toarray()
    np.testing.assert_array_equal(jax.jit(sx.bucketed_to_dense)(B), Xd)
    rng = np.random.RandomState(1)
    W = rng.randn(d + 1, k).astype(np.float32)
    r = rng.randn(n, k).astype(np.float32)
    Xa = np.hstack([Xd, np.ones((n, 1), np.float32)])
    op = sx.LinearOperator(B, True)
    np.testing.assert_allclose(op.matvec(W), Xa @ W, atol=5e-5)
    np.testing.assert_allclose(op.matvec(W[:, 0]), Xa @ W[:, 0], atol=5e-5)
    np.testing.assert_allclose(op.rmatvec(r), Xa.T @ r, atol=5e-5)
    rows = rng.randint(0, n, 32)
    g = rng.randn(32, k).astype(np.float32)
    # jitted, as the fits run them: eagerly every bucket's every op
    # compiles on its own
    np.testing.assert_allclose(jax.jit(op.row_matvec)(rows, W),
                               Xa[rows] @ W, atol=5e-5)
    np.testing.assert_allclose(jax.jit(op.row_rmatvec)(rows, g),
                               Xa[rows].T @ g, atol=5e-5)

    def loss(Wl, X):
        return jnp.sum(jnp.tanh(
            sx.LinearOperator(X, True).matvec(Wl)) * r)

    lanes = rng.randn(4, d + 1, k).astype(np.float32)
    batched = jax.jit(jax.vmap(jax.value_and_grad(loss), (0, None)))
    v, gr = batched(lanes, B)
    v_ref, gr_ref = batched(lanes, jnp.asarray(Xd))
    np.testing.assert_allclose(v, v_ref, rtol=2e-5)
    np.testing.assert_allclose(gr, gr_ref, atol=5e-5)


def _head_case(case):
    """A CSR whose head :func:`sx.pack_csr_buckets` builds on the
    device from its stored elements, as scipy may hand it over."""
    X = skewed_csr(seed=9, n=200, d=1500, heavy=(600, 400))
    rng = np.random.RandomState(4)
    if case == "canonical":
        assert X.has_canonical_format
        return X
    if case == "no_head":
        return _structured("no_head")[0]
    coo = X.tocoo()
    rows, cols, vals = coo.row, coo.col, coo.data
    if case == "explicit_zeros":
        # stored zeros of both signs, in the head's columns and out
        vals = vals.copy()
        vals[rng.rand(vals.size) < 0.2] = 0.0
        vals[rng.rand(vals.size) < 0.05] = -0.0
        X = sp.csr_matrix((vals, (rows, cols)), shape=X.shape)
        assert (X.data == 0).sum() > 100
        return X
    if case == "empty_rows":
        keep = ~np.isin(rows, [5, 6, 7, 150, 199])
        X = sp.csr_matrix((vals[keep], (rows[keep], cols[keep])),
                          shape=X.shape)
        assert (np.diff(X.indptr) == 0).sum() == 5
        return X
    assert case.startswith("duplicates")
    # a third of the elements stored again and again, every row's
    # elements in no order: scipy keeps each copy until summed
    again = rng.rand(vals.size) < 0.3
    rows = np.r_[rows, rows[again], rows[again]]
    cols = np.r_[cols, cols[again], cols[again]]
    vals = np.r_[vals, rng.rand(2 * again.sum())].astype(
        np.float64 if case.endswith("float64") else np.float32)
    if case.endswith("float64"):
        # values float32 cannot hold, whose sums round once in float64
        vals = vals * (1 + 1e-9) + 1e-12
    order = np.lexsort((rng.rand(rows.size), rows))
    indptr = np.r_[0, np.cumsum(np.bincount(rows, minlength=X.shape[0]))]
    X = sp.csr_matrix((vals[order], cols[order], indptr), shape=X.shape)
    assert not X.has_canonical_format and X.nnz > 1.5 * coo.nnz
    return X


@pytest.mark.parametrize("case", [
    "canonical", "duplicates", "duplicates_float64", "explicit_zeros",
    "empty_rows", "no_head"])
def test_head_built_on_the_device_is_toarray_to_the_bit(case):
    """The dense head that ``pack_csr_buckets`` scatters on the device
    from the CSR's stored elements is ``X[:, head_cols].toarray()`` in
    float32 bit for bit — duplicates summed as scipy sums them, stored
    zeros of either sign, rows with no element — and the products over
    the bucketed matrix stay the dense ones."""
    X = _head_case(case)
    n, d = X.shape
    B = sx.pack_csr_buckets(X)
    if case == "no_head":
        assert B.head is None and B.head_cols is None
    else:
        assert isinstance(B.head, jax.Array)
        assert B.head.dtype == jnp.float32
        want = np.asarray(X[:, B.head_cols].toarray(), np.float32)
        got = np.asarray(B.head)
        assert got.shape == want.shape == (n, B.head_cols.size)
        assert np.array_equal(got.view(np.int32), want.view(np.int32))
        assert B.head_nnz == np.isin(X.indices, B.head_cols).sum()
    B = jax.tree_util.tree_map(jnp.asarray, B)
    Xd = np.asarray(X.toarray(), np.float32)
    # the buckets hold a duplicate's copies apart, each in float32, and
    # their sum is the device's: within a rounding of scipy's
    np.testing.assert_allclose(jax.jit(sx.bucketed_to_dense)(B), Xd,
                               rtol=1e-6)
    rng = np.random.RandomState(1)
    W = rng.randn(d + 1, 4).astype(np.float32)
    r = rng.randn(n, 4).astype(np.float32)
    Xa = np.hstack([Xd, np.ones((n, 1), np.float32)])
    op = sx.LinearOperator(B, True)
    np.testing.assert_allclose(op.matvec(W), Xa @ W, atol=5e-5)
    np.testing.assert_allclose(op.rmatvec(r), Xa.T @ r, atol=5e-5)


def test_pack_builds_no_dense_head_on_the_host(monkeypatch):
    """At the text cell's proportions (a generated corpus of 2,000
    documents: 70 % of its elements in a head of an eighth of the
    columns), ``pack_for_fit`` never densifies on the host — scipy's
    ``toarray`` / ``todense`` are not called, and the pack's host peak
    is a fraction of the head's ``n * h * 4`` bytes — and its
    ``pack_x`` span says the head was built on the device from under a
    twentieth of those bytes."""
    import tracemalloc

    from chipbench import datagen_text
    from skdist_tpu.obs import trace as obs_trace

    X, _ = datagen_text.bag_of_words(3, 2000, 30000, 20, 120000,
                                     len_sigma=1.6, len_cap=3000)
    assert sx.pack_decision(X)[1] == "bucketed"
    h = sx.head_columns(X).size
    dense_bytes = X.shape[0] * h * 4

    def densified(*a, **k):
        raise AssertionError("the head was densified on the host")

    for cls in (sp.csr_matrix, sp.csc_matrix, sp.coo_matrix):
        monkeypatch.setattr(cls, "toarray", densified)
        monkeypatch.setattr(cls, "todense", densified)
    sx.pack_for_fit(X)  # the build's program compiles outside the count
    obs_trace.clear()
    obs_trace.set_enabled(True)
    tracemalloc.start()
    try:
        B = sx.pack_for_fit(X)
        _, peak = tracemalloc.get_traced_memory()
        spans = [e for e in obs_trace.events()
                 if e[1] == "X" and e[0] == "pack_x"]
    finally:
        tracemalloc.stop()
        obs_trace.set_enabled(False)
        obs_trace.clear()
    assert isinstance(B, sx.BucketedX) and B.head.shape == (X.shape[0], h)
    assert B.head_nnz > 0.6 * X.nnz
    assert peak < dense_bytes / 4, (peak, dense_bytes)
    (span,) = spans
    args = span[5]
    assert args["head_on_device"] is True and args["head_cols"] == h
    assert args["head_sent_bytes"] == 8 * sx.head_sent_slots(B.head_nnz)
    assert 8 * B.head_nnz <= args["head_sent_bytes"] < dense_bytes / 20


def test_pack_span_of_a_matrix_with_no_head():
    """A padded pair and a bucketed matrix without a head send nothing
    for one: ``head_on_device`` False, ``head_sent_bytes`` 0."""
    from skdist_tpu.obs import trace as obs_trace

    even = sp.random(300, 4096, density=0.01, format="csr",
                     dtype=np.float32, random_state=np.random.RandomState(3))
    headless = _head_case("no_head")
    assert sx.pack_decision(headless)[1] == "bucketed"
    obs_trace.clear()
    obs_trace.set_enabled(True)
    try:
        packed = [sx.pack_for_fit(X) for X in (even, headless)]
        spans = [e[5] for e in obs_trace.events()
                 if e[1] == "X" and e[0] == "pack_x"]
    finally:
        obs_trace.set_enabled(False)
        obs_trace.clear()
    assert type(packed[0]) is sx.PackedX and packed[1].head is None
    assert [(a["head_on_device"], a["head_sent_bytes"]) for a in spans] == [
        (False, 0), (False, 0)]


def test_search_over_a_device_head_matches_the_dense_search():
    """``DistGridSearchCV`` over a skewed CSR with a head — built on
    the device, placed without a copy on a one-device mesh — scores
    as the search over the dense matrix does, and a second search of
    the same shapes compiles nothing: the head's build is one program
    a shape, as every round's is."""
    from test_sparse_fit import _skewed_problem

    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.parallel import TPUBackend, compile_cache
    from skdist_tpu.parallel import backend as backend_mod

    X, y = _skewed_problem(seed=17, n=240, d=3000, k=3)
    head_shape = (240, sx.head_columns(X).size)
    assert head_shape[1]
    grid = {"C": [0.05, 0.5, 5.0]}

    def search(M):
        return DistGridSearchCV(
            LogisticRegression(max_iter=60, engine="xla"), grid,
            backend=TPUBackend(devices=jax.devices()[:1]), cv=3,
            scoring="accuracy", error_score="raise").fit(M, y)

    heads, placed = [], []
    real_head, real_scoped = sx._dense_head, backend_mod._put_mesh_scoped

    def dense_head(*a):
        out = real_head(*a)
        heads.append(out.unsafe_buffer_pointer())
        return out

    def put_mesh_scoped(x, sharding):
        out = real_scoped(x, sharding)
        if isinstance(x, jax.Array) and x.shape == head_shape:
            placed.append(out.addressable_shards[0].data
                          .unsafe_buffer_pointer())
        return out

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sx, "_dense_head", dense_head)
        mp.setattr(backend_mod, "_put_mesh_scoped", put_mesh_scoped)
        packed = search(X)
    # the head went to the mesh as it lay, and every later placement
    # (the dispatch's of the placed X) left it there: the same buffer
    assert len(heads) == 1 and placed and set(placed) == set(heads)
    dense = search(np.asarray(X.toarray(), np.float32))
    for key in ("mean_test_score", "split0_test_score", "split2_test_score"):
        np.testing.assert_allclose(
            np.asarray(packed.cv_results_[key]),
            np.asarray(dense.cv_results_[key]), atol=1e-5)
    before = compile_cache.snapshot()["backend_compiles"]
    again = search(X)
    assert compile_cache.snapshot()["backend_compiles"] == before
    np.testing.assert_array_equal(
        np.asarray(again.cv_results_["mean_test_score"]),
        np.asarray(packed.cv_results_["mean_test_score"]))
    np.testing.assert_array_equal(again.best_estimator_.coef_,
                                  packed.best_estimator_.coef_)


def _representation(name, d=600):
    """``(X as the operator takes it, the same matrix dense)`` for a
    dense array, a padded pair and a ``BucketedX`` of ``d`` columns."""
    X = skewed_csr(seed=4, n=96, d=d, heavy=(250, 120))
    Xd = X.toarray()
    if name == "dense":
        return jnp.asarray(Xd), Xd
    if name == "padded":
        return sx.PackedX(*map(jnp.asarray, sx.pack_csr_rows(X)), d), Xd
    return jax.tree_util.tree_map(jnp.asarray, sx.pack_csr_buckets(X)), Xd


@pytest.mark.parametrize("fit_intercept", [True, False],
                         ids=["intercept", "plain"])
@pytest.mark.parametrize("representation", ["dense", "padded", "bucketed"])
def test_operator_owns_the_flat_layout_of_a_weight_matrix(
        representation, fit_intercept):
    """``flat`` / ``matrix`` round-trip a ``(p, k)`` weight matrix at a
    ``p`` that no tile divides, for every representation; ``logits``
    of ``weights``' view, its VJP and ``coef_sq_sum`` equal the dense
    float32 expressions, one lane and vmapped. A dense or padded-pair
    X keeps ``W.reshape(-1)``; a ``BucketedX``'s lies classes-major in
    rows of the next multiple of 128, zeros after each class's ``p``
    weights, and its VJP leaves those zeros exactly zero."""
    d, k, lanes = 600, 5, 3
    X, Xd = _representation(representation, d)
    n = Xd.shape[0]
    Xa = np.hstack([Xd, np.ones((n, 1), np.float32)]) if fit_intercept \
        else Xd
    op = sx.LinearOperator(X, fit_intercept)
    p = d + int(fit_intercept)
    assert op.p == p and p % sx.ROW_ALIGN
    rng = np.random.RandomState(2)
    W = rng.randn(lanes, p, k).astype(np.float32)
    flat = np.asarray(jax.vmap(op.flat)(jnp.asarray(W)))
    assert flat.shape == (lanes, op.flat_size(k))
    np.testing.assert_array_equal(
        jax.vmap(lambda w: op.matrix(w, k))(jnp.asarray(flat)), W)
    if representation == "bucketed":
        width = 640
        assert (op.width, op.flat_size(k), op.class_axis) == (
            width, k * width, 0)
        rows = flat.reshape(lanes, k, width)
        np.testing.assert_array_equal(rows[:, :, :p],
                                      W.transpose(0, 2, 1))
        assert not rows[:, :, p:].any()
        assert op.weights(jnp.asarray(flat[0]), k).shape == (k, width)
    else:
        assert op.flat_size(k) == p * k
        np.testing.assert_array_equal(flat, W.reshape(lanes, -1))
        np.testing.assert_array_equal(
            op.weights(jnp.asarray(flat[0]), k), W[0])
    ax = op.class_axis
    r = rng.randn(lanes, k, n).astype(np.float32)

    def value(wflat, r):
        Wv = op.weights(wflat, k)
        z = op.logits(Wv)
        z = z if ax == 0 else z.T
        return jnp.sum(jnp.tanh(z) * r) + op.coef_sq_sum(Wv), z

    with jax.default_matmul_precision("highest"):
        one = jax.jit(jax.value_and_grad(value, has_aux=True))
        many = jax.jit(jax.vmap(jax.value_and_grad(value, has_aux=True)))
        (v, z), g = many(jnp.asarray(flat), jnp.asarray(r))
        (v0, z0), g0 = one(jnp.asarray(flat[0]), jnp.asarray(r[0]))
    for got_v, got_z, got_g, Wl, rl in [(v0, z0, g0, W[0], r[0])] + list(
            zip(v, z, g, W, r)):
        want_z = (Xa.astype(np.float64) @ Wl).T
        np.testing.assert_allclose(got_z, want_z, atol=5e-5)
        want_v = np.sum(np.tanh(want_z) * rl) + np.sum(Wl[:d] ** 2.0)
        np.testing.assert_allclose(got_v, want_v, rtol=2e-5)
        want_g = Xa.T.astype(np.float64) @ (
            (1.0 - np.tanh(want_z) ** 2) * rl).T
        want_g[:d] += 2.0 * Wl[:d]
        np.testing.assert_allclose(op.matrix(got_g, k), want_g, atol=2e-4)
        # what the layout pads has no gradient
        np.testing.assert_array_equal(op.flat(op.matrix(got_g, k)), got_g)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of everything nested in it, in
    order."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("transposed", [False, True],
                         ids=["forward", "transposed"])
def test_vmapped_product_is_one_transpose_on_each_side_of_the_gathers(
        transposed):
    """A round's multinomial product over a ``BucketedX``: the lanes
    join the classes by a reshape, ONE transpose makes the gathers'
    operand — rows of ``lanes·k`` contiguous floats — and one takes the
    result back; nothing is moved lane by lane (no
    ``dynamic_update_slice``, no transpose of three axes)."""
    d, k, lanes = 600, 5, 7
    B, Xd = _representation("bucketed", d)
    n = Xd.shape[0]
    op = sx.LinearOperator(B, True)

    def logits(wflat):
        return op.logits(op.weights(wflat, k))

    w = jnp.zeros((lanes, op.flat_size(k)), jnp.float32)
    if transposed:
        def product(r):
            return jax.vmap(lambda wl, rl: jax.vjp(logits, wl)[1](rl)[0])(
                w, r)
        jaxpr = jax.make_jaxpr(product)(jnp.zeros((lanes, k, n)))
        wide_in, wide_out = (n, lanes * k), (op.width, lanes * k)
    else:
        jaxpr = jax.make_jaxpr(jax.vmap(logits))(w)
        wide_in, wide_out = (op.width, lanes * k), (n, lanes * k)
    if transposed:
        # the forward product of ``jax.vjp`` is traced too: the
        # transposed one is what follows its last equation
        names = [e.primitive.name for e in _eqns(jaxpr.jaxpr)]
        assert names.count("transpose") == 4
    eqns = [e for e in _eqns(jaxpr.jaxpr)]
    if transposed:
        second = [i for i, e in enumerate(eqns)
                  if e.primitive.name == "transpose"][2]
        eqns = eqns[second:]
    names = [e.primitive.name for e in eqns]
    assert "dynamic_update_slice" not in names
    turns = [e for e in eqns if e.primitive.name == "transpose"]
    assert [(e.invars[0].aval.shape, e.outvars[0].aval.shape)
            for e in turns] == [(wide_in[::-1], wide_in),
                                (wide_out, wide_out[::-1])]
    gathers = [i for i, n_ in enumerate(names) if n_ == "gather"]
    assert gathers and names.index("transpose") < gathers[0]
    assert gathers[-1] < len(names) - 1 - names[::-1].index("transpose")
    for i in gathers:
        assert eqns[i].invars[0].aval.shape[-1] == lanes * k


def _sliced_multinomial_fit(X, y, Cs, max_iter=8, n_slice=4):
    """The estimator's own sliced fit (``init``, ``step`` ...,
    ``finalize``) vmapped over the lanes ``Cs``: the carry after the
    last slice and the fitted parameters."""
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.models.linear import (
        _freeze, maybe_exact_matmuls, prepare_fit_X)

    est = LogisticRegression(max_iter=max_iter, engine="xla")
    data, meta = est._prep_fit_data(
        prepare_fit_X(X, LogisticRegression), y, None)
    kernels = LogisticRegression._build_fit_slice_kernels(
        meta, _freeze(est._static_config(meta)), n_slice)
    args = (jax.tree_util.tree_map(jnp.asarray, data["X"]),
            jnp.asarray(data["y"]), jnp.asarray(data["sw"]))

    def run(C):
        hyper = {"C": C, "tol": jnp.float32(1e-4)}
        carry = kernels["init"](*args, hyper)
        for _ in range(-(-max_iter // n_slice) - 1):
            carry = kernels["step"](*args, hyper, carry)
        return carry, kernels["finalize"](*args, hyper, carry)

    return jax.jit(jax.vmap(maybe_exact_matmuls(LogisticRegression, run)))(
        jnp.asarray(Cs, jnp.float32))


def test_padding_is_zero_after_a_fit():
    """A multinomial solve over a ``BucketedX`` runs on the aligned
    layout from its start to ``unpack``: every padding entry of the
    fitted carry (iterate, gradient, both histories) is exactly zero,
    lane by lane, and the fit is the fit over the densified X — the
    same iterations, coefficients within float32 rounding."""
    from test_sparse_fit import _skewed_problem

    X, y = _skewed_problem(seed=11, d=3000, k=4)
    Cs = [0.02, 0.1, 0.5]
    carry, params = _sliced_multinomial_fit(X, y, Cs)
    p, k, width = 3001, 4, 3072
    assert carry["w"].shape == (3, k * width)
    assert carry["S"].shape == (3, 10, k * width)
    for key in ("w", "g", "S", "Y"):
        leaf = np.asarray(carry[key])
        leaf = leaf.reshape(leaf.shape[:-1] + (k, width))
        assert leaf[..., :p].any() and not leaf[..., p:].any(), key
    assert params["W"].shape == (3, p, k)
    dense_carry, dense = _sliced_multinomial_fit(
        np.asarray(X.toarray(), np.float32), y, Cs)
    assert dense_carry["w"].shape == (3, p * k)
    np.testing.assert_array_equal(carry["it"], dense_carry["it"])
    np.testing.assert_array_equal(carry["it"], 8)
    np.testing.assert_allclose(params["W"], dense["W"], atol=2e-4)


@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
def test_multinomial_fit_on_a_bucketed_x_equals_the_densified_fit(
        warm, monkeypatch):
    """``LogisticRegression.fit`` over a skewed CSR (packed
    ``BucketedX``) gives the densified fit's ``coef_`` and
    ``intercept_``, shapes and values — started cold, and warm from a
    seed in sklearn's shapes, which the problem takes into the
    operator's layout (``LinearOperator.flat``)."""
    from skdist_tpu.models import LogisticRegression
    from test_sparse_fit import _skewed_problem

    X, y = _skewed_problem(seed=12, d=3000, k=4)
    assert sx.pack_decision(X)[1] == "bucketed"
    seeds = {}
    if warm:
        rng = np.random.RandomState(3)
        seeds = dict(
            coef_init=0.05 * rng.randn(4, 3000).astype(np.float32),
            intercept_init=0.1 * rng.randn(4).astype(np.float32))

    def fit(M, **kw):
        return LogisticRegression(max_iter=300, tol=1e-5, engine="xla",
                                  **kw).fit(M, y, **seeds)

    packed = fit(X)
    assert packed._meta.get("x_format") == "packed"
    dense = fit(np.asarray(X.toarray(), np.float32))
    assert packed.coef_.shape == (4, 3000)
    assert packed.intercept_.shape == (4,)
    assert 10 < packed.n_iter_ < 300 and 10 < dense.n_iter_ < 300
    np.testing.assert_allclose(packed.coef_, dense.coef_, atol=5e-4)
    np.testing.assert_allclose(packed.intercept_, dense.intercept_,
                               atol=5e-4)
    if warm:
        # a solve of no iterations hands the seed back as it came
        still = LogisticRegression(max_iter=0, engine="xla").fit(
            X, y, **seeds)
        np.testing.assert_array_equal(still.coef_, seeds["coef_init"])
        np.testing.assert_array_equal(still.intercept_,
                                      seeds["intercept_init"])


def test_bf16_contract_on_the_bucketed_products():
    """``matmul_dtype='bfloat16'`` on a ``BucketedX``: the operands
    round to bf16, the row sums accumulate in float32, the head is one
    bf16 pass with float32 accumulation and the intercept's column of
    ones stays float32 — the padded pair's contract
    (``test_sparse_fit.py``), in both directions. Whether a bucket's
    PRODUCTS round to bf16 before the sum, as the pair's do op by op,
    is the compiler's to say inside the fused scan (XLA may keep the
    float32 product: excess precision), so either sum is the contract;
    the references are summed in float64."""
    X = skewed_csr(seed=4, n=200, d=2000, heavy=(700, 400))
    B = jax.tree_util.tree_map(jnp.asarray, sx.pack_csr_buckets(X))
    assert B.head is not None and 0 < B.head_nnz < B.nnz
    n, d = X.shape
    k = 3
    rng = np.random.RandomState(6)
    W = rng.randn(d + 1, k).astype(np.float32)
    r = rng.randn(n, k).astype(np.float32)
    in_head = np.zeros(d, bool)
    in_head[np.asarray(B.head_cols)] = True
    coo = X.tocoo()

    def bf16(a):
        return jnp.asarray(a).astype(jnp.bfloat16)

    def products(operand_rows, rounded):
        """bf16(val) * bf16(operand row) a stored element, in the
        buckets ``rounded`` to bf16 or not; exact in the head's
        accumulation either way."""
        v, o = bf16(coo.data)[:, None], bf16(operand_rows)
        exact = np.asarray(v.astype(jnp.float32) * o.astype(jnp.float32),
                           np.float64)
        if not rounded:
            return exact
        return np.where(in_head[coo.col][:, None], exact,
                        np.asarray((v * o).astype(jnp.float32), np.float64))

    def forward(rounded):
        out = np.zeros((n, k))
        np.add.at(out, coo.row, products(W[coo.col], rounded))
        return out + W[d]

    def backward(rounded):
        out = np.zeros((d + 1, k))
        np.add.at(out, coo.col, products(r[coo.row], rounded))
        out[d] = r.astype(np.float64).sum(0)
        return out

    op = sx.LinearOperator(B, True, matmul_dtype="bfloat16")
    exact = sx.LinearOperator(B, True)
    grad = jax.grad(lambda w: jnp.sum(op.matvec(w) * r))(jnp.asarray(W))
    for got, want, f32 in ((op.matvec(W), forward, exact.matvec(W)),
                           (op.rmatvec(r), backward, exact.rmatvec(r)),
                           # the solvers' gradient IS the transpose
                           (grad, backward, exact.rmatvec(r))):
        got, f32 = np.asarray(got), np.asarray(f32)
        scale = np.maximum(1.0, np.abs(f32))
        assert min(np.max(np.abs(got - want(rounded)) / scale)
                   for rounded in (True, False)) < 2e-6
        # the contract's own precision class, not float32's
        assert 1e-4 < np.max(np.abs(got - f32) / scale) < 0.1


def test_even_rows_pack_to_the_one_padded_pair():
    """No skew: ``pack_for_fit`` answers the ``PackedX`` it always did,
    bit for bit ``pack_csr_rows``."""
    X = sp.random(300, 4096, density=0.01, format="csr",
                  dtype=np.float32, random_state=np.random.RandomState(3))
    assert sx.pack_decision(X)[:2] == (True, "packed")
    packed = sx.pack_for_fit(X)
    assert type(packed) is sx.PackedX
    idx, val = sx.pack_csr_rows(X)
    assert np.array_equal(packed.idx, idx) and np.array_equal(packed.val, val)
    assert packed.m == np.diff(X.indptr).max()
    widths, counts = sx.bucket_widths(np.diff(X.indptr))
    assert sum(counts) == 300 and widths == sorted(widths)


def test_search_on_a_skewed_csr_stays_packed_and_matches_the_reference(
        monkeypatch):
    """``DistGridSearchCV`` over a skewed 20-class CSR through the
    batched path: the matrix is never densified, the round stats book
    the packing, and every answer is within the stated gaps of the
    benchmark's plain reference (float32 vectors on its side too: the
    gaps are the rounding of two float32 solvers on 400 rows, where a
    fit left at its start would read 1e-1)."""
    from chipbench import datagen_text
    from chipbench.reference.softmax_lr import stratified_folds
    from chipbench.reference.softmax_lr_sparse import SparseSoftmaxLR
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression, linear
    from skdist_tpu.parallel import TPUBackend

    X, y = datagen_text.bag_of_words(
        7, 400, 30000, 20, 24000, len_cap=3000, topic_terms=100,
        len_sigma=1.6)
    assert sx.pack_decision(X)[1] == "bucketed"

    def no_dense(*a, **k):
        raise AssertionError("the matrix was densified")

    monkeypatch.setattr(sx, "sparse_to_dense_f32", no_dense)
    monkeypatch.setattr(linear, "sparse_to_dense_f32", no_dense)
    Cs, cv = [0.1, 10.0], 3
    backend = TPUBackend(devices=jax.devices()[:1])
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=25, tol=1e-4), {"C": Cs},
        backend=backend, cv=cv, scoring="neg_log_loss", refit=False,
        error_score="raise").fit(X, y)
    stats = backend.last_round_stats
    assert stats["kernel_mode"] == "packed_gather"
    assert X.nnz < stats["x_nnz"] <= 2 * X.nnz < stats["x_slots"]
    got = np.array([[gs.cv_results_[f"split{f}_test_score"][c]
                     for f in range(cv)] for c in range(len(Cs))])
    ref = SparseSoftmaxLR(X, y, 20)
    want = np.array(ref.fold_scores(
        stratified_folds(y, cv), [(f, C) for C in Cs for f in range(cv)],
        25, 1e-4)).reshape(len(Cs), cv)
    gap = np.abs(got - want)
    assert np.median(gap) < 2e-4 and gap.max() < 2e-3, gap


def test_search_refit_takes_the_packed_matrix(monkeypatch):
    """The refit fits the matrix the search packed: one pack a search,
    and the same model as a fit of the CSR itself."""
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.parallel import TPUBackend

    X = skewed_csr(seed=3)
    y = np.arange(X.shape[0]) % 3
    packs = []
    pack = sx.pack_csr_buckets
    monkeypatch.setattr(
        sx, "pack_csr_buckets", lambda M: packs.append(1) or pack(M))
    est = LogisticRegression(max_iter=15, tol=1e-4)
    gs = DistGridSearchCV(
        est, {"C": [0.1, 1.0]}, cv=3, scoring="neg_log_loss",
        backend=TPUBackend(devices=jax.devices()[:1]),
        error_score="raise").fit(X, y)
    assert len(packs) == 1 and not hasattr(gs, "_rounds_X_")
    alone = LogisticRegression(max_iter=15, tol=1e-4,
                               **gs.best_params_).fit(X, y)
    assert len(packs) == 2
    np.testing.assert_array_equal(gs.best_estimator_.coef_, alone.coef_)
    assert gs.best_estimator_.predict(X).shape == y.shape


@pytest.mark.parametrize("n_devices", [1, 4])
def test_search_refit_runs_over_the_placed_buckets(monkeypatch, n_devices):
    """The packed tree crosses once a fit too: every leaf of the
    bucketed X is placed by the search, the dispatch and the refit's
    kernel are handed those buffers (the refit the first replica of
    each), the placement under ``refit`` is labels and weights, and
    the model is the standalone fit's to the bit. The head does not
    cross from the host at all: the pack builds it on the device, and
    its first replica is the buffer the pack built."""
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression, linear
    from skdist_tpu.obs import trace as obs_trace
    from skdist_tpu.parallel import TPUBackend, backend as backend_mod

    X = skewed_csr(seed=4)
    y = np.arange(X.shape[0]) % 3
    crossed, kernel_X, heads = [], [], []
    real_scoped, real_kernel, real_head = (
        backend_mod._put_mesh_scoped, linear.get_kernel, sx._dense_head)

    def dense_head(*a):
        out = real_head(*a)
        heads.append(out.unsafe_buffer_pointer())
        return out

    def put_mesh_scoped(x, sharding):
        out = real_scoped(x, sharding)
        if isinstance(x, np.ndarray):
            # (read now: a round's task slices are donated)
            crossed.append(pointers(out)[0][0])
        return out

    def get_kernel(cls, which, meta, static):
        kernel = real_kernel(cls, which, meta, static)
        if which != "fit":
            return kernel
        return lambda X_, *rest: kernel_X.append(X_) or kernel(X_, *rest)

    def pointers(tree):
        return [[s.data.unsafe_buffer_pointer()
                 for s in leaf.addressable_shards]
                for leaf in jax.tree_util.tree_leaves(tree)]

    monkeypatch.setattr(backend_mod, "_put_mesh_scoped", put_mesh_scoped)
    monkeypatch.setattr(linear, "get_kernel", get_kernel)
    monkeypatch.setattr(sx, "_dense_head", dense_head)
    obs_trace.clear()
    obs_trace.set_enabled(True)
    try:
        est = LogisticRegression(max_iter=15, tol=1e-4, engine="xla")
        gs = DistGridSearchCV(
            est, {"C": [0.1, 1.0]}, cv=3, scoring="neg_log_loss",
            backend=TPUBackend(devices=jax.devices()[:n_devices]),
            error_score="raise").fit(X, y)
        spans = [e for e in obs_trace.events() if e[1] == "X"]
    finally:
        obs_trace.set_enabled(False)
        obs_trace.clear()
    monkeypatch.undo()
    (over,) = kernel_X
    assert isinstance(over, sx.BucketedX)
    # every leaf the refit's kernel ran over is the first replica of
    # a host array that crossed (once: the spans below), but the head,
    # which is the one the pack built
    leaves = [p[0] for p in pointers(over)]
    assert heads == [pointers(over.head)[0][0]]
    assert set(leaves) - set(heads) <= set(crossed)
    assert len(leaves) == len(set(leaves))
    assert all(len(leaf.devices()) == 1
               for leaf in jax.tree_util.tree_leaves(over))
    packed_bytes = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(over))
    refit = next(e for e in spans if e[0] == "refit")
    places = [e for e in spans if e[0] == "place_shared"]
    assert [e[5]["bytes"] == packed_bytes for e in places] == [
        True, False, False]
    assert refit[5]["x_placed"] is True
    assert refit[5]["bytes"] == places[-1][5]["bytes"] == 8 * len(y)
    assert places[-1][5]["parent_id"] == refit[5]["span_id"]
    alone = LogisticRegression(max_iter=15, tol=1e-4, engine="xla",
                               **gs.best_params_).fit(X, y)
    np.testing.assert_array_equal(gs.best_estimator_.coef_, alone.coef_)
    np.testing.assert_array_equal(gs.best_estimator_.intercept_,
                                  alone.intercept_)


def _products(jaxpr, min_size, count=0):
    """Number of ``dot_general`` equations with an operand of at least
    ``min_size`` elements in a jaxpr and everything nested in it."""
    for eqn in jaxpr.eqns:
        count += eqn.primitive.name == "dot_general" and any(
            np.prod(v.aval.shape) >= min_size for v in eqn.invars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count = _products(sub, min_size, count)
    return count


def test_line_search_along_a_ray_takes_the_same_path():
    """A loss that offers ``ray(w, d) -> (along, value_and_grad_at)``
    with ``along(t) == loss(w + t * d)`` is searched through it: the
    same iterates as the plain search up to rounding, the same count
    of evaluations asked for (``nfev`` has one meaning on both
    branches), and three products an iteration where the plain search
    holds one more inside its halving loop."""
    from skdist_tpu.models.solvers import (
        LBFGS_CARRY_KEYS, _lbfgs_body, lbfgs_carry_init, lbfgs_resume)

    rng = np.random.RandomState(0)
    A = jnp.asarray(rng.randn(200, 12).astype(np.float32))
    y = jnp.asarray((rng.rand(200) < 0.5).astype(np.float32))

    def from_logits(z, w):
        return jnp.sum(jax.nn.softplus(z) - y * z) + 0.05 * jnp.dot(w, w)

    def plain(w):
        return from_logits(A @ w, w)

    def with_ray(w):
        return from_logits(A @ w, w)

    calls = []

    def ray(w, d):
        calls.append(1)
        z0, dz = A @ w, A @ d

        def along(t):
            return from_logits(z0 + t * dz, w + t * d)

        def value_and_grad_at(t):
            f, (r, g_reg) = jax.value_and_grad(from_logits, (0, 1))(
                z0 + t * dz, w + t * d)
            return f, A.T @ r + g_reg

        return along, value_and_grad_at

    with_ray.ray = ray
    w0 = jnp.zeros(12, jnp.float32)
    # six iterations: from the seventh on the decrease is under a
    # float32 ulp of the loss (134.94221), and which trial step
    # passes the Armijo test is the rounding's choice on both branches
    n_it = 6
    out, products = [], []
    for loss in (plain, with_ray):
        carry = lbfgs_carry_init(loss, w0, max_iter=n_it, tol=1e-6)
        body = _lbfgs_body(loss, jax.value_and_grad(loss), n_it, 1e-6, 10,
                           20)
        products.append(_products(jax.make_jaxpr(body)(
            tuple(carry[key] for key in LBFGS_CARRY_KEYS)).jaxpr, A.size))
        out.append(lbfgs_resume(loss, carry, n_it, max_iter=n_it, tol=1e-6))
    a, b = out
    assert calls and int(a["it"]) == int(b["it"]) == n_it
    np.testing.assert_allclose(a["w"], b["w"], atol=2e-5)
    assert int(b["nfev"]) == int(a["nfev"]) > 2 * n_it + 1
    # the plain search: the first trial, the halving loop's body and
    # the value-and-gradient's two; the ray: its two and the transpose
    assert products == [4, 3]
