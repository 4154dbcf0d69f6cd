"""
Native fasthash kernel tests: C/Python byte-parity, analyzers,
unicode, chunking, and integration with FastHashingVectorizer.
"""

import numpy as np
import pytest

from skdist_tpu.native import hash_documents, native_available
from skdist_tpu.preprocessing import FastHashingVectorizer

DOCS = [
    "Hello world foo",
    "the quick brown Fox jumps over",
    "hashing text 123 fast_tokens",
    "",
    "a",  # below min token length for word analyzer
]


@pytest.mark.parametrize("analyzer,ngram", [
    ("word", (1, 1)), ("word", (1, 3)), ("char_wb", (2, 4)),
])
def test_c_python_parity(analyzer, ngram):
    kw = dict(n_features=512, ngram_range=ngram, analyzer=analyzer)
    a = hash_documents(DOCS, **kw)
    b = hash_documents(DOCS, force_python=True, **kw)
    assert (a != b).nnz == 0
    assert a.shape == (len(DOCS), 512)


def test_unicode_parity():
    docs = ["héllo wörld ünïcode", "日本語 テスト text", "emoji 🙂 doc"]
    a = hash_documents(docs, n_features=256, ngram_range=(1, 2))
    b = hash_documents(docs, n_features=256, ngram_range=(1, 2),
                       force_python=True)
    assert (a != b).nnz == 0


def test_binary_and_counts():
    docs = ["dog dog dog cat"]
    counts = hash_documents(docs, n_features=64, binary=False)
    binary = hash_documents(docs, n_features=64, binary=True)
    assert counts.max() == 3.0
    assert binary.max() == 1.0
    assert (counts.indices == binary.indices).all()


def test_vectorizer_transform_and_norm():
    v = FastHashingVectorizer(n_features=128, ngram_range=(1, 2), norm="l2")
    out = v.fit_transform(DOCS[:3])
    rows = np.asarray(out.power(2).sum(axis=1)).ravel()
    np.testing.assert_allclose(rows, 1.0, atol=1e-6)
    raw = FastHashingVectorizer(n_features=128, norm=None).transform(DOCS[:3])
    assert raw.max() >= 1.0
    with pytest.raises(ValueError):
        v.transform("just a string")


def test_vectorizer_chunking_identical():
    v1 = FastHashingVectorizer(n_features=64, chunksize=2)
    v2 = FastHashingVectorizer(n_features=64, chunksize=None)
    a, b = v1.transform(DOCS), v2.transform(DOCS)
    assert (a != b).nnz == 0


def test_native_actually_built():
    # the build environment ships a C toolchain; the native path must
    # genuinely compile there (fallback is only for hostile installs)
    assert native_available()


def test_in_pipeline_with_search(clf_data):
    from sklearn.pipeline import Pipeline
    from sklearn.linear_model import LogisticRegression as SkLR

    docs = ["good fine great", "bad awful poor", "great nice", "awful sad"] * 15
    y = np.array([1, 0, 1, 0] * 15)
    pipe = Pipeline([
        ("vec", FastHashingVectorizer(n_features=256, ngram_range=(1, 2))),
        ("clf", SkLR(max_iter=200)),
    ]).fit(docs, y)
    assert pipe.score(docs, y) == 1.0


def test_csr_to_dense_matches_scipy():
    """Native multithreaded densifier vs scipy toarray: identical
    output (incl. duplicate-entry accumulation), f32 C-contiguous."""
    from scipy import sparse

    from skdist_tpu.native import csr_to_dense_f32

    rng = np.random.RandomState(7)
    X = sparse.random(300, 90, density=0.05, random_state=rng,
                      format="coo", dtype=np.float64)
    # duplicate coordinates must accumulate, like scipy CSR
    rows = np.concatenate([X.row, X.row[:7]])
    cols = np.concatenate([X.col, X.col[:7]])
    vals = np.concatenate([X.data, X.data[:7]])
    Xd = sparse.coo_matrix((vals, (rows, cols)), shape=X.shape)
    ref = np.asarray(Xd.tocsr().toarray(), dtype=np.float32)

    out = csr_to_dense_f32(Xd)
    assert out.dtype == np.float32 and out.flags["C_CONTIGUOUS"]
    np.testing.assert_array_equal(out, ref)

    # int64 index path
    c = Xd.tocsr()
    c.indices = c.indices.astype(np.int64)
    c.indptr = c.indptr.astype(np.int64)
    np.testing.assert_array_equal(csr_to_dense_f32(c), ref)

    # fallback contract
    np.testing.assert_array_equal(
        csr_to_dense_f32(Xd, force_python=True), ref
    )

    # empty matrix edge
    empty = sparse.csr_matrix((0, 5), dtype=np.float32)
    assert csr_to_dense_f32(empty).shape == (0, 5)


def test_as_dense_f32_sparse_routes_through_densifier(monkeypatch):
    from scipy import sparse

    import skdist_tpu.native as native_mod
    from skdist_tpu.models.linear import as_dense_f32

    calls = []
    real = native_mod.csr_to_dense_f32

    def spy(X, **kw):
        calls.append(X.shape)
        return real(X, **kw)

    monkeypatch.setattr(native_mod, "csr_to_dense_f32", spy)

    rng = np.random.RandomState(8)
    # large enough to cross the native threshold (>= 2^22 cells)
    X = sparse.random(2100, 2048, density=0.005, random_state=rng,
                      format="csr", dtype=np.float32)
    out = as_dense_f32(X)
    assert calls == [(2100, 2048)], "large sparse must route natively"
    np.testing.assert_array_equal(out, np.asarray(X.toarray(), np.float32))

    # small sparse stays on the plain toarray path
    small = sparse.random(50, 40, density=0.1, random_state=rng,
                          format="csr", dtype=np.float32)
    as_dense_f32(small)
    assert calls == [(2100, 2048)], "small sparse must NOT route natively"


def test_as_dense_f32_1d_sparse_array():
    """1-D scipy sparse arrays (csr_array of a vector) have a 1-tuple
    shape; the native-path size guard must not index shape[1]
    (regression: IndexError before the len(shape)==2 check)."""
    import scipy.sparse as sparse

    from skdist_tpu.models.linear import as_dense_f32

    try:
        v = sparse.csr_array(np.arange(5, dtype=np.float64))
    except (TypeError, ValueError):  # scipy without 1-D sparse support
        import pytest

        pytest.skip("scipy version lacks 1-D sparse arrays")
    out = as_dense_f32(v)
    assert out.shape == (5, 1) and out.dtype == np.float32
    np.testing.assert_array_equal(out.ravel(), np.arange(5, dtype=np.float32))


_MINI_C = """
#include <Python.h>
static PyObject *answer(PyObject *self, PyObject *args) {
    return PyLong_FromLong(%d);
}
static PyMethodDef methods[] = {
    {"answer", answer, METH_NOARGS, ""}, {NULL, NULL, 0, NULL}};
static struct PyModuleDef mod = {
    PyModuleDef_HEAD_INIT, "_mini", "", -1, methods};
PyMODINIT_FUNC PyInit__mini(void) { return PyModule_Create(&mod); }
"""


def test_build_is_keyed_on_source_digest(tmp_path, monkeypatch):
    """A copied ``_build/`` must never serve an old binary: the .so is
    named after a digest of its source and flags, so an edited source
    rebuilds even when its mtime is OLDER than the stale binary's (what
    a tree copy leaves behind), and the stale binary is removed."""
    import os

    import pytest

    from skdist_tpu import native

    monkeypatch.setattr(native, "__file__", str(tmp_path / "__init__.py"))
    monkeypatch.setitem(native._EXT_FLAGS, "mini", ())
    src = tmp_path / "mini.c"

    def load():
        native._EXTS.pop("mini", None)
        mod = native._load_ext("mini")
        if mod is None:
            pytest.skip(f"no C compiler: {native._EXT_STATUS['mini']}")
        return mod.answer(), dict(native._EXT_STATUS["mini"])

    src.write_text(_MINI_C % 1)
    assert load() == (1, {"loaded": True, "built": True, "error": None})
    assert load()[1]["built"] is False  # same digest: no rebuild
    (first,) = os.listdir(tmp_path / "_build")
    src.write_text(_MINI_C % 2)
    os.utime(src, (0, 0))  # older than the stale binary
    assert load() == (2, {"loaded": True, "built": True, "error": None})
    assert os.listdir(tmp_path / "_build") != [first]
    assert len(os.listdir(tmp_path / "_build")) == 1
    native._EXTS.pop("mini", None)
    native._EXT_STATUS.pop("mini", None)
