"""
Native (C) runtime components with build-on-demand and pure-Python
fallbacks.

The reference framework's native compute lived in its dependencies
(sklearn Cython, Spark JVM, pyarrow C++ — SURVEY §2.2). skdist_tpu's
device compute is XLA; the host-side hot path that merits native code
is text featurisation (the Encoderizer's hashing vectorisers). This
package compiles ``fasthash.c`` with the system compiler on first use
(no pip/network needed) and falls back to a byte-identical pure-Python
implementation when no compiler is available.
"""

import hashlib
import os
import subprocess
import sysconfig
import tempfile
import threading

import numpy as np

#: the C extensions of this package and their extra compiler flags
_EXT_FLAGS = {
    "fasthash": (),
    "hist_tree": ("-pthread",),
    "densify": ("-pthread",),
}
_EXTS = {}
#: name -> {"loaded", "built", "error"}: what :func:`_load_ext` found,
#: kept because it swallows the exception itself
_EXT_STATUS = {}
_LOAD_LOCK = threading.Lock()


def _load_ext(name):
    """Import the compiled module ``_<name>`` (from ``<name>.c``),
    building it on first use.

    Any failure anywhere (read-only tree, missing compiler, truncated
    artifact) returns None so callers take the pure-Python path — the
    fallback contract must survive hostile installs; the reason is
    kept for :func:`ext_status`. Builds go to a temp file and are
    renamed into place (atomic on POSIX) so concurrent processes never
    load a half-written .so.
    """
    with _LOAD_LOCK:
        if name in _EXTS:
            return _EXTS[name]
        status = {"loaded": False, "built": False, "error": None}
        try:
            mod = _load_ext_inner(name, status)
            status["loaded"] = True
        except Exception as exc:
            mod = None
            detail = getattr(exc, "stderr", None)
            status["error"] = f"{type(exc).__name__}: {exc}" + (
                f" | {detail.decode(errors='replace')[-400:]}"
                if detail else ""
            )
        _EXTS[name] = mod
        _EXT_STATUS[name] = status
        return mod


def _load_ext_inner(name, status):
    import importlib.util

    here = os.path.dirname(__file__)
    build_dir = os.path.join(here, "_build")
    os.makedirs(build_dir, exist_ok=True)
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    src = os.path.join(here, f"{name}.c")
    cc = os.environ.get("CC", "cc")
    flags = ["-O3", "-shared", "-fPIC", *_EXT_FLAGS[name]]
    # the binary is keyed on a digest of what it is built FROM, so a
    # _build/ copied along with an edited source (mtimes do not survive
    # a copy) can never serve an old binary
    with open(src, "rb") as f:
        digest = hashlib.sha256(
            f.read() + repr((cc, flags, suffix)).encode()
        ).hexdigest()[:16]
    so_path = os.path.join(build_dir, f"_{name}-{digest}{suffix}")
    if not os.path.exists(so_path):
        include = sysconfig.get_paths()["include"]
        fd, tmp_path = tempfile.mkstemp(suffix=suffix, dir=build_dir)
        os.close(fd)
        try:
            subprocess.run(
                [cc, *flags, f"-I{include}", src, "-o", tmp_path],
                check=True, capture_output=True, timeout=120,
            )
            os.replace(tmp_path, so_path)
        finally:
            if os.path.exists(tmp_path):
                os.unlink(tmp_path)
        status["built"] = True
        for stale in os.listdir(build_dir):
            if (stale.startswith((f"_{name}-", f"_{name}."))
                    and stale != os.path.basename(so_path)):
                try:
                    os.unlink(os.path.join(build_dir, stale))
                except OSError:
                    pass
    spec = importlib.util.spec_from_file_location(f"_{name}", so_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ext_status():
    """Load (building where needed) every C extension of the package
    and say what happened to each: ``{name: {"loaded", "built",
    "error"}}`` — ``built`` is whether THIS process compiled it,
    ``error`` why it did not load (a missing compiler, say). Library
    callers keep their pure-Python fallbacks; an entry point that wants
    to show what it ran on prints this."""
    for name in _EXT_FLAGS:
        _load_ext(name)
    return {name: dict(_EXT_STATUS[name]) for name in _EXT_FLAGS}


def _load_native():
    return _load_ext("fasthash")


# ---------------------------------------------------------------------------
# pure-Python reference implementation (byte-identical contract)
# ---------------------------------------------------------------------------

def _fnv1a(data: bytes) -> int:
    h = 2166136261
    for b in data:
        h ^= b
        h = (h * 16777619) & 0xFFFFFFFF
    return h


def _is_token_char(b):
    return (
        (0x61 <= b <= 0x7A) or (0x41 <= b <= 0x5A) or (0x30 <= b <= 0x39)
        or b == 0x5F or b >= 0x80
    )


def _tokenize(text: bytes):
    toks, i, n = [], 0, len(text)
    while i < n:
        while i < n and not _is_token_char(text[i]):
            i += 1
        s = i
        while i < n and _is_token_char(text[i]):
            i += 1
        if i - s >= 2:
            toks.append(text[s:i])
    return toks


def _words_all(text: bytes):
    toks, i, n = [], 0, len(text)
    while i < n:
        while i < n and not _is_token_char(text[i]):
            i += 1
        s = i
        while i < n and _is_token_char(text[i]):
            i += 1
        if i > s:
            toks.append(text[s:i])
    return toks


def _py_hash_doc(text, n_features, nlo, nhi, analyzer, lowercase):
    if lowercase:
        # ASCII-only lowering, matching the C kernel
        text = bytes(
            b + 32 if 0x41 <= b <= 0x5A else b for b in text.encode("utf-8")
        )
    else:
        text = text.encode("utf-8")
    hashes = []
    if analyzer == 0:  # word
        toks = _tokenize(text)
        for n in range(nlo, nhi + 1):
            if n > len(toks):
                break
            for t in range(len(toks) - n + 1):
                gram = b" ".join(toks[t:t + n])
                hashes.append(_fnv1a(gram) % n_features)
    else:  # char_wb
        for w in _words_all(text):
            padded = b" " + w + b" "
            for n in range(nlo, nhi + 1):
                if n > len(padded):
                    break
                for p in range(len(padded) - n + 1):
                    hashes.append(_fnv1a(padded[p:p + n]) % n_features)
    return hashes


def _py_hash_docs(docs, n_features, nlo, nhi, analyzer, lowercase, binary):
    indptr = [0]
    indices, data = [], []
    for doc in docs:
        hashes = sorted(
            _py_hash_doc(doc, n_features, nlo, nhi, analyzer, lowercase)
        )
        i = 0
        while i < len(hashes):
            j = i
            while j < len(hashes) and hashes[j] == hashes[i]:
                j += 1
            indices.append(hashes[i])
            data.append(1.0 if binary else float(j - i))
            i = j
        indptr.append(len(indices))
    return (
        np.asarray(indptr, dtype=np.int64),
        np.asarray(indices, dtype=np.uint32),
        np.asarray(data, dtype=np.float32),
    )


def hash_documents(docs, n_features=2**12, ngram_range=(1, 1),
                   analyzer="word", lowercase=True, binary=False,
                   force_python=False):
    """Hash text documents → scipy CSR matrix (n_docs, n_features).

    Uses the compiled C kernel when available; the Python path is
    byte-identical (tested).
    """
    from scipy import sparse

    docs = [d if isinstance(d, str) else str(d) for d in docs]
    nlo, nhi = ngram_range
    a = {"word": 0, "char_wb": 1}[analyzer]
    native = None if force_python else _load_native()
    if native is not None:
        bi, bidx, bdat = native.hash_docs(
            docs, n_features, nlo, nhi, a, int(lowercase), int(binary)
        )
        indptr = np.frombuffer(bi, dtype=np.int64)
        indices = np.frombuffer(bidx, dtype=np.uint32)
        data = np.frombuffer(bdat, dtype=np.float32)
    else:
        indptr, indices, data = _py_hash_docs(
            docs, n_features, nlo, nhi, a, lowercase, binary
        )
    return sparse.csr_matrix(
        (data, indices.astype(np.int32), indptr),
        shape=(len(docs), n_features),
    )


def native_available():
    return _load_native() is not None


# ---------------------------------------------------------------------------
# per-level tree histograms (hist_tree.c) — host forest engine
# ---------------------------------------------------------------------------

def hist_tree_available():
    return _load_ext("hist_tree") is not None


def hist_level(hist, XbT, node_rel, W, cls=None, yv=None, act=None,
               n_threads=None, force_python=False):
    """Accumulate (Tb, d, nl, B, C) per-level histograms (zero-fills
    ``hist`` first; callers pass ``np.empty``).

    ``XbT`` (d, n) uint8 feature-major bins, ``node_rel`` (Tb, n) int32
    (-1 = sample not at this level), ``W`` (Tb, n) f32 weights, and
    exactly one of ``cls`` (n,) int32 / ``yv`` (n,) f32 selects the
    classification / regression channel layout (see hist_tree.c).
    ``act`` (Tb, d) uint8 skips features no node of that tree sampled
    this level (their slabs are left zeroed — callers must not read
    stats from a skipped feature). The numpy fallback is semantically
    identical (tested).
    """
    Tb, d, nl, B, C = hist.shape
    n = XbT.shape[1]
    mod = None if force_python else _load_ext("hist_tree")
    if mod is not None:
        if n_threads is None:
            n_threads = min(16, os.cpu_count() or 1)
        mod.hist_level(
            hist, XbT, node_rel, W,
            None if cls is None else cls, None if yv is None else yv,
            None if act is None else act,
            n, d, Tb, nl, B, C, int(n_threads),
        )
        return hist
    # ---- numpy fallback: one bincount-style scatter per (tree, feature)
    hist[:] = 0.0
    flat = hist.reshape(Tb, d, nl * B, C)
    for t in range(Tb):
        w = W[t]
        live = (node_rel[t] >= 0) & (w != 0)
        if not live.any():
            continue
        nr = node_rel[t][live].astype(np.int64)
        wa = w[live]
        if cls is not None:
            ch = np.zeros((live.sum(), C), np.float32)
            ch[np.arange(len(wa)), cls[live]] = wa
            ch[:, C - 1] = (wa > 0)
        else:
            ya = yv[live]
            ch = np.stack([wa, wa * ya, wa * ya * ya,
                           (wa > 0).astype(np.float32)], axis=1)
        for f in range(d):
            if act is not None and not act[t, f]:
                continue
            seg = nr * B + XbT[f][live]
            np.add.at(flat[t, f], seg, ch)
    return hist


def forest_walk_native(Xb, trees, max_depth, mode="predict",
                       n_threads=None):
    """Predict-side tree traversal via the C kernel, or None when it
    is unavailable (callers then use the XLA walker).

    ``Xb`` (n, d) uint8 bins, ``trees`` the stacked pytree
    ``{feat, thr, is_split, leaf}`` (T, N)-shaped. ``mode='predict'``
    returns the (n, K) mean leaf vector; ``'apply'`` the (n, T) final
    node ids — matching ``models/forest.py::_forest_walker`` exactly
    (a node stays put once a non-split node is reached)."""
    mod = _load_ext("hist_tree")
    if mod is None:
        return None
    feat = np.ascontiguousarray(trees["feat"], np.int32)
    thr = np.ascontiguousarray(trees["thr"], np.int32)
    sp = np.ascontiguousarray(trees["is_split"], np.uint8)
    T, N = feat.shape
    if 2 ** (int(max_depth) + 1) - 1 > N:
        # a depth the arrays weren't built for (e.g. max_depth mutated
        # after fit) would walk past the buffers in C; the XLA walker's
        # clipped indexing degrades gracefully — fall through to it
        return None
    n, d = Xb.shape
    Xb = np.ascontiguousarray(Xb, np.uint8)
    if n_threads is None:
        n_threads = min(16, os.cpu_count() or 1)
    if mode == "predict":
        leaf = np.ascontiguousarray(trees["leaf"], np.float32)
        K = leaf.shape[2]
        out = np.empty((n, K), np.float32)
        mod.forest_walk(Xb, feat, thr, sp, leaf, out, None,
                        n, d, T, N, K, int(max_depth), int(n_threads))
        return out
    out = np.empty((n, T), np.int32)
    mod.forest_walk(Xb, feat, thr, sp, None, None, out,
                    n, d, T, N, 1, int(max_depth), int(n_threads))
    return out


def best_splits_native(hist, fmask, urand, K, classification,
                       min_samples_leaf, n_threads=None):
    """Per-(tree, node) best split from a level histogram via the C
    kernel, or None when the kernel is unavailable / the channel count
    exceeds its accumulator cap (callers then run the numpy scoring
    path). Returns ``(gain, f, t, cnt_l, cnt_r)`` each (Tb, nl)."""
    mod = _load_ext("hist_tree")
    Tb, d, nl, B, C = hist.shape
    if mod is None or C > 256 or K > 256:
        return None
    if n_threads is None:
        n_threads = min(16, os.cpu_count() or 1)
    gain = np.empty((Tb, nl), np.float32)
    bf = np.empty((Tb, nl), np.int32)
    bt = np.empty((Tb, nl), np.int32)
    cl = np.empty((Tb, nl), np.float32)
    cr = np.empty((Tb, nl), np.float32)
    mod.best_splits(
        hist, None if fmask is None else fmask,
        None if urand is None else urand,
        gain, bf, bt, cl, cr,
        Tb, d, nl, B, C, K, int(classification),
        float(min_samples_leaf), int(n_threads),
    )
    return gain, bf, bt, cl, cr


# ---------------------------------------------------------------------------
# multithreaded CSR -> dense f32 (densify.c)
# ---------------------------------------------------------------------------

def csr_to_dense_f32(X, force_python=False, n_threads=None):
    """Densify a scipy sparse matrix to a C-contiguous float32 array.

    The host-side boundary feeding the device: TPU has no general
    sparse matmul, so hashed-text CSR matrices densify before
    ``device_put``. The C kernel partitions rows across threads
    (zero-fill + scatter per block, GIL released); the fallback is
    scipy's single-threaded ``toarray``. Duplicate entries accumulate
    in both paths (scipy CSR semantics).
    """
    csr = X.tocsr()
    n_rows, n_cols = csr.shape
    mod = None if force_python else _load_ext("densify")
    if mod is None or n_rows == 0 or n_cols == 0:
        return np.ascontiguousarray(csr.toarray(), dtype=np.float32)
    data = np.ascontiguousarray(csr.data, dtype=np.float32)
    indices = np.ascontiguousarray(csr.indices)
    if indices.dtype not in (np.int32, np.int64):
        indices = indices.astype(np.int64)
    indptr = np.ascontiguousarray(csr.indptr, dtype=np.int64)
    out = np.empty((n_rows, n_cols), dtype=np.float32)
    if n_threads is None:
        n_threads = min(16, os.cpu_count() or 1)
    mod.csr_to_dense(
        out, data, indices, indptr, n_rows, n_cols,
        indices.dtype.itemsize, int(n_threads),
    )
    return out
