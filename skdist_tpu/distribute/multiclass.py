"""
Distributed one-vs-rest / one-vs-one multiclass strategies.

Re-design of the reference (``/root/reference/skdist/distribute/
multiclass.py:195-475``). The reference ships one Spark task per class
column (OvR, multiclass.py:316-331) or per class pair (OvO,
multiclass.py:440-459), each running ``_fit_binary`` (109-152) with
optional negative down-sampling (``_negatives_mask``, 76-106), a
constant-class fallback (175-192), and nested-search unwrapping
(``_use_best_estimator``, 65-73).

TPU-first design:

- **batched path** (JAX base estimators): the class (or class-pair)
  axis becomes the vmapped task axis of ONE compiled binary-fit
  program. Per-task label vectors are derived *on device* from the
  shared label matrix (``y_bin = Y[:, c]``); OvO's per-pair row subsets
  — shape-dynamic in the reference — become 0/1 sample-weight masks
  (SURVEY §7.3 hard part 1). Negative down-sampling is EXACT: per-class
  keep masks with the host path's target arithmetic and RandomState
  draw are precomputed on host and ride the task axis, so both paths
  of one estimator share sampling semantics.
- **generic path**: any sklearn-compatible estimator, one host task per
  class/pair with exact reference semantics (exact down-sampling,
  ConstantPredictor fallback, best_estimator_ unwrapping).

After fit both paths expose the same artifacts: ``estimators_`` (plain
picklable per-class estimators), ``classes_``, and sklearn-compatible
``predict`` / ``predict_proba`` / ``decision_function``.
"""

import warnings

import numpy as np

from ..base import BaseEstimator, ClassifierMixin, clone, strip_runtime
from ..parallel import (
    faults,
    iterative_fit_supported,
    parse_partitions,
    prefers_host_engine,
    resolve_backend,
)
from ..utils.validation import (
    check_estimator_backend,
    check_is_fitted,
    full_length_sample_weight,
    safe_split,
)

__all__ = ["DistOneVsRestClassifier", "DistOneVsOneClassifier"]


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _n_rows(X):
    return X.shape[0] if hasattr(X, "shape") else len(X)


def _warn_nonfinite_lanes(stacked, describe, what):
    """Lane-quarantine guard over a batched multiclass fit's stacked
    params (coef/intercept leaves): a non-finite lane means that
    sub-problem's solve diverged. Unlike the CV search there is no
    ``error_score`` contract to map onto — the host path would carry
    the same NaN params silently — so the guard makes the failure LOUD
    (a ``FitFailedWarning`` naming the affected classes/pairs) instead
    of letting predict-side argmax over NaN columns pick silently.
    ``describe(lane_index) -> str`` labels a poisoned lane.
    ``SKDIST_FAULT_GUARD=0`` disables."""
    if not faults.guard_enabled():
        return
    bad = faults.nonfinite_lanes(stacked)
    if bad is None or not bad.any():
        return
    from .search import FitFailedWarning

    idxs = np.where(bad)[0]
    names = ", ".join(describe(int(i)) for i in idxs[:5])
    if len(idxs) > 5:
        names += ", ..."
    faults.record("lanes_quarantined", int(bad.sum()))
    warnings.warn(
        f"{int(bad.sum())} batched {what} fit(s) produced non-finite "
        f"parameters (diverged lanes: {names}); their predictions "
        "will be unreliable. Check hyperparameters / data scaling.",
        FitFailedWarning,
    )


class _ConstantPredictor(BaseEstimator):
    """Degenerate single-class column fallback (reference
    multiclass.py:175-192)."""

    def fit(self, X, y):
        self.y_ = np.asarray(y).ravel()[:1]
        return self

    def predict(self, X):
        return np.repeat(self.y_, _n_rows(X))

    def decision_function(self, X):
        return np.repeat(float(2 * self.y_[0] - 1), _n_rows(X))

    def predict_proba(self, X):
        p = float(self.y_[0])
        return np.repeat([[1.0 - p, p]], _n_rows(X), axis=0)


def _use_best_estimator(est):
    """Unwrap a fitted nested SearchCV to its best_estimator_, carrying
    cv_results_ along as strings (reference multiclass.py:65-73).

    Only search-style wrappers are unwrapped. A fitted
    DistFeatureEliminator also exposes ``best_estimator_``, but its
    inner model was refit on the masked feature subset — unwrapping it
    would feed full-width X to a reduced-width model at predict time,
    so eliminators (marked by ``best_features_``) stay wrapped."""
    if not hasattr(est, "best_estimator_") or hasattr(est, "best_features_"):
        return est
    inner = est.best_estimator_
    if hasattr(est, "cv_results_"):
        import pandas as pd

        df = pd.DataFrame(est.cv_results_)
        inner.cv_results_ = {c: df[c].astype(str).tolist() for c in df.columns}
    return inner


def _negatives_mask(X, y, max_negatives=None, random_state=None, method="ratio"):
    """Exact negative down-sampling (reference multiclass.py:76-106):
    ratio = fraction of negatives kept; multiplier = mult*n_pos kept."""
    if max_negatives is None:
        return X, y
    pos_mask = np.asarray(y) == 1
    n_pos = int(pos_mask.sum())
    n_neg = int((~pos_mask).sum())
    if method == "ratio":
        target = max_negatives if isinstance(max_negatives, int) else int(
            round(max_negatives * n_neg)
        )
    elif method == "multiplier":
        target = int(max_negatives * n_pos)
    else:
        raise ValueError("Unknown method. Options are 'ratio' or 'multiplier'.")
    if target >= n_neg:
        return X, y
    rng = np.random.RandomState(random_state)
    neg_idx = np.where(~pos_mask)[0]
    keep_neg = rng.choice(neg_idx, size=target, replace=False)
    keep = np.concatenate([np.where(pos_mask)[0], keep_neg])
    rng.shuffle(keep)
    Xs = X[keep] if hasattr(X, "shape") else [X[i] for i in keep]
    return Xs, np.asarray(y)[keep]


def _fit_binary(estimator, X, y, fit_params=None, classes=None,
                max_negatives=None, random_state=None, method="ratio"):
    """Host-path single binary fit (reference multiclass.py:109-152)."""
    fit_params = fit_params or {}
    unique_y = np.unique(y)
    if len(unique_y) == 1:
        if classes is not None:
            c = 0 if unique_y[0] in (-1, 0) else 1
            warnings.warn(
                f"Label {classes[c]} is present in all training examples."
            )
        return _ConstantPredictor().fit(X, y)
    est = clone(estimator)
    Xs, ys = _negatives_mask(
        X, y, max_negatives=max_negatives, random_state=random_state,
        method=method,
    )
    est.fit(Xs, ys, **fit_params)
    return _use_best_estimator(est)


def _label_matrix(y, classes=None):
    """y (labels / sequences-of-labels / binary indicator matrix) →
    (Y, classes, multilabel). Y is int32 (n, k).

    Only *sequences of label collections* are multilabel; 1-D object
    arrays of scalar labels (e.g. strings) are ordinary multiclass —
    iterating a string as characters is never intended."""
    if _is_sequence_of_seqs(y):
        from sklearn.preprocessing import MultiLabelBinarizer

        mlb = MultiLabelBinarizer()
        Y = mlb.fit_transform(y)
        return Y.astype(np.int32), mlb.classes_, True
    y = np.asarray(y)
    if y.ndim == 2 and y.shape[1] == 1:
        # column vector of labels, as sklearn ravels (with a warning)
        warnings.warn(
            "A column-vector y was passed; ravelling to 1-D labels.",
        )
        y = y.ravel()
    if y.ndim == 2:
        # binary indicator matrix — validate it actually is one
        if not np.isin(np.unique(y), (0, 1)).all():
            raise ValueError(
                "2-D y must be a binary indicator matrix (values 0/1); "
                "got other values. For multiclass labels pass 1-D y."
            )
        classes = np.arange(y.shape[1]) if classes is None else classes
        return y.astype(np.int32), np.asarray(classes), True
    classes, y_idx = np.unique(y, return_inverse=True)
    Y = np.zeros((len(y), len(classes)), dtype=np.int32)
    Y[np.arange(len(y)), y_idx] = 1
    return Y, classes, False


def _is_sequence_of_seqs(y):
    try:
        first = y[0] if not hasattr(y, "iloc") else y.iloc[0]
    except (TypeError, IndexError, KeyError):
        return False
    return isinstance(first, (list, tuple, set, frozenset))


def _binary_prep(est, X_arr):
    """(X_dev, meta, aux) for the {0,1} binary sub-problems of any
    estimator implementing the batched-fit contract: calls the
    estimator's own _prep_fit_data with a synthetic two-class y so
    data-dependent context (tree bin edges etc.) is built exactly as a
    real binary fit would build it; X stays host-staged and is placed
    (and, with reuse_broadcast, cached) once by the backend's
    batched_map. Returns (None,)*3 if prep fails or the
    estimator is not a classifier (no 'classes' meta) — those take the
    generic host path."""
    if getattr(est, "_estimator_type", None) != "classifier":
        # non-classifier base: no binary batched form — bail before
        # paying any host->device transfer (duck-typed so sklearn's
        # ClassifierMixin qualifies too)
        return None, None, None
    try:
        data, meta = est._prep_fit_data(
            X_arr, np.arange(len(X_arr), dtype=np.int64) % 2, None
        )
    except Exception as exc:
        warnings.warn(
            f"batched binary prep failed ({type(exc).__name__}: {exc}); "
            "falling back to the per-task host path"
        )
        return None, None, None
    if "classes" not in meta:
        return None, None, None
    from ..models.linear import extract_aux

    return data["X"], meta, extract_aux(data)


def _binary_confidence(est, X):
    """Signed margin for a fitted binary estimator: 1-D decisions pass
    through; two-column decisions (e.g. naive Bayes per-class
    log-likelihoods) become their difference; otherwise proba-0.5."""
    if hasattr(est, "decision_function"):
        dec = np.asarray(est.decision_function(X))
        if dec.ndim == 1:
            return dec
        if dec.ndim == 2 and dec.shape[1] == 1:
            return dec[:, 0]
        if dec.ndim == 2 and dec.shape[1] == 2:
            return dec[:, 1] - dec[:, 0]
    return np.asarray(est.predict_proba(X))[:, 1] - 0.5


def _iterative_fit_spec(est_cls, meta, static, n_slice, derive,
                        fallback_kernel, fallback_key, key,
                        outputs=None, rung_score=None):
    """Wrap an estimator's iteration-sliced fit kernels for the
    convergence-compacted backend entry point — the ONE
    ``batched_map_iterative`` spec builder shared by the CV search,
    OvR/OvO, and the feature eliminator. ``derive(shared, task) ->
    (X, y, w, hyper, aux)`` supplies the per-task sub-problem (CV
    fold-masked weights, OvR class column, OvO pair mask, eliminate's
    feature-masked X); ``key`` must bake in everything ``derive`` /
    ``outputs`` / ``rung_score`` depend on beyond (est_cls, static,
    meta). Returns an ``IterativeKernelSpec`` whose kernels are
    memoised on ``key``.

    ``outputs(params, shared, task)`` optionally post-processes the
    finalized fit params into the spec's outputs (the CV search scores
    them on the fold masks here); None returns the raw params (the
    OvR/OvO per-class artifact). ``rung_score(params, shared, task) ->
    scalar`` additionally equips the spec with the adaptive (ASHA)
    rung evaluator: params are shaped from the LIVE carry through the
    family's ``score_params`` kernel (``solvers.carry_iterate``
    contract — the current iterate is a valid model at every slice
    boundary), then scored; the backend compiles it as a fourth jit
    entry so carries never leave the device."""
    from ..models.linear import maybe_exact_matmuls
    from ..parallel import IterativeKernelSpec, compile_cache

    def build():
        ks = est_cls._build_fit_slice_kernels(meta, static, n_slice)
        f_init = maybe_exact_matmuls(est_cls, ks["init"])
        f_step = maybe_exact_matmuls(est_cls, ks["step"])
        f_fin = maybe_exact_matmuls(est_cls, ks["finalize"])

        def init(shared, task):
            X, y, w, hyper, aux = derive(shared, task)
            return f_init(X, y, w, hyper, aux)

        def step(shared, task, carry):
            X, y, w, hyper, aux = derive(shared, task)
            return f_step(X, y, w, hyper, carry, aux)

        def finalize(shared, task, carry):
            X, y, w, hyper, aux = derive(shared, task)
            params = f_fin(X, y, w, hyper, carry, aux)
            if outputs is None:
                return params
            return outputs(params, shared, task)

        parts = {"init": init, "step": step, "finalize": finalize,
                 "keys": ks["finalize_keys"],
                 "count_keys": ks.get("count_keys", ())}
        if rung_score is not None:
            f_live = maybe_exact_matmuls(
                est_cls, ks.get("score_params", ks["finalize"])
            )

            def score(shared, task, carry):
                X, y, w, hyper, aux = derive(shared, task)
                params = f_live(X, y, w, hyper, carry, aux)
                return rung_score(params, shared, task)

            parts["score"] = score
        return parts

    parts = compile_cache.kernel_memo(("spec",) + tuple(key), build)
    return IterativeKernelSpec(
        parts["init"], parts["step"], parts["finalize"], parts["keys"],
        fallback=fallback_kernel, fallback_cache_key=fallback_key,
        score=parts.get("score"), count_keys=parts["count_keys"],
    )


def _make_fitted_binary(base, params_slice, meta, static_names=None):
    """Materialise a fitted JAX binary estimator from a kernel params
    slice (the batched path's per-class artifact)."""
    est = clone(base)
    est._params = params_slice
    est._meta = meta
    est.n_features_in_ = meta["n_features"]
    est.classes_ = meta["classes"]
    return est


# ---------------------------------------------------------------------------
# OvR
# ---------------------------------------------------------------------------

class DistOneVsRestClassifier(BaseEstimator, ClassifierMixin):
    """One-vs-rest with class-axis fan-out (reference multiclass.py:195-362).

    Parameters mirror the reference: ``max_negatives``/``method``/
    ``random_state`` control negative down-sampling per binary problem,
    ``norm`` optionally L1/L2-normalises stacked probabilities
    (reference multiclass.py:337-362), ``backend`` replaces ``sc``.
    """

    def __init__(self, estimator, backend=None, partitions="auto",
                 max_negatives=None, method="ratio", norm=None,
                 random_state=None, n_jobs=None, verbose=0):
        self.estimator = estimator
        self.backend = backend
        self.partitions = partitions
        self.max_negatives = max_negatives
        self.method = method
        self.norm = norm
        self.random_state = random_state
        self.n_jobs = n_jobs
        self.verbose = verbose

    def fit(self, X, y=None, **fit_params):
        check_estimator_backend(self, self.verbose)
        if self.method not in ("ratio", "multiplier"):
            raise ValueError(
                "Unknown method. Options are 'ratio' or 'multiplier'."
            )
        backend = resolve_backend(self.backend, n_jobs=self.n_jobs)
        from ..data import is_chunked

        if is_chunked(X):
            return self._fit_streamed(backend, X, y, fit_params)
        if y is None:
            raise TypeError(
                "fit requires y (only a ChunkedDataset carries labels)"
            )
        Y, classes, multilabel = _label_matrix(y)
        self.classes_ = classes
        self.multilabel_ = multilabel
        # 2-class non-multilabel: ONE binary estimator on the positive
        # column, like the reference's LabelBinarizer (which emits a
        # single column for binary y); the negative class is derived on
        # the predict side as the complement. Fitting both complementary
        # columns would double the work and break [1-p, p] semantics.
        self.binary_ = (not multilabel) and Y.shape[1] == 2
        if self.binary_:
            Y = Y[:, 1:]
        n_classes = Y.shape[1]

        done = None
        sw, sw_ok = full_length_sample_weight(fit_params, _n_rows(X))
        if sw_ok:
            done = self._try_batched(backend, X, Y, sample_weight=sw)
        if done is None:
            self._fit_generic(backend, X, Y, fit_params)
        self.estimator = clone(self.estimator)
        strip_runtime(self)
        return self

    # -- streamed out-of-core path --------------------------------------
    def _fit_streamed(self, backend, dataset, y, fit_params):
        """OvR over a ChunkedDataset: the class axis is the task axis
        of ONE streamed fit — every class's binary problem consumes the
        same block stream (labels binarised ON DEVICE per task from the
        encoded label vector), so the data is read once per solver
        pass regardless of the class count. No host fallback exists
        for out-of-core input, so unsupported configurations raise
        with the resident-path remedy."""
        import jax.numpy as jnp

        from ..models.linear import (
            _annotate_stream_meta, _freeze, hyper_float,
        )
        from ..models.streaming import stream_fit_tasks

        est = self.estimator
        est_cls = type(est)
        if getattr(est_cls, "_stream_fit_kind", None) is None:
            raise ValueError(
                f"{est_cls.__name__} has no streamed fit driver; "
                "ChunkedDataset OvR supports the linear families"
            )
        if self.max_negatives is not None:
            raise ValueError(
                "max_negatives down-sampling needs per-class row draws "
                "over resident X; not supported with ChunkedDataset "
                "input"
            )
        if getattr(est, "class_weight", None) is not None:
            raise ValueError(
                "class_weight does not map onto the streamed {0,1} "
                "binary sub-problems; fit with resident X for "
                "class-weighted OvR"
            )
        if getattr(est, "engine", None) == "host":
            raise ValueError(
                "engine='host' cannot fit a ChunkedDataset; use "
                "engine='auto'/'xla'"
            )
        if y is None:
            y = dataset.load_y()
        y = np.asarray(y)
        if y.ndim != 1 and not (y.ndim == 2 and y.shape[1] == 1):
            raise ValueError(
                "multilabel y is not supported with ChunkedDataset "
                "input (pass 1-D multiclass labels)"
            )
        y = y.reshape(-1)
        sw, sw_ok = full_length_sample_weight(fit_params, dataset.n_rows)
        if not sw_ok:
            raise ValueError(
                "streamed OvR supports only a full-length sample_weight "
                f"fit param; got {sorted(fit_params)}"
            )
        if sw is None:
            sw = dataset.load_sw()
        classes, y_enc = np.unique(y, return_inverse=True)
        y_enc = y_enc.astype(np.int32)
        self.classes_ = classes
        self.multilabel_ = False
        k = len(classes)
        self.binary_ = k == 2
        from ..models.linear import prepare_sample_weight

        sw_arr = prepare_sample_weight(sw, dataset.n_rows)
        # binary sub-problem meta: classes {0, 1} exactly like the
        # resident batched path's _binary_prep
        meta = _annotate_stream_meta({
            "n_features": dataset.n_features,
            "classes": np.arange(2, dtype=np.int64),
            "n_classes": 2,
            "cw_arr": None,
        }, dataset)
        static = _freeze(est._static_config(meta))
        # task axis = class columns (the positive column only for
        # binary y, mirroring the resident reduction)
        task_cls = np.array([1], np.int32) if self.binary_ else \
            np.arange(k, dtype=np.int32)
        counts = np.bincount(y_enc, minlength=k)
        n = dataset.n_rows
        degenerate = (counts == 0) | (counts == n)
        live = np.asarray(
            [c for c in task_cls if not degenerate[c]], np.int32
        )
        estimators = [None] * len(task_cls)
        if live.size:
            hyper = {
                name: np.full(
                    live.size, float(hyper_float(getattr(est, name))),
                    np.float32,
                )
                for name in est_cls._hyper_names
            }
            if est_cls._stream_fit_kind == "gram" and "alpha" not in hyper:
                hyper["alpha"] = np.full(
                    live.size, float(hyper_float(est.alpha)), np.float32
                )
            task_args = {"hyper": hyper, "cls": live}

            def derive(block, task):
                yb = (block["y"] == task["cls"]).astype(jnp.int32)
                return block["X"], yb, block["sw"], task["hyper"]

            params = stream_fit_tasks(
                backend, est_cls, meta, static, dataset,
                {"y": y_enc, "sw": sw_arr}, task_args, derive=derive,
                key_extra=("ovr",),
            )
            _warn_nonfinite_lanes(
                params,
                lambda i: f"class {classes[live[i]]!r}",
                "one-vs-rest",
            )
            for pos, cls_idx in enumerate(live):
                sl = {
                    key: np.asarray(v)[pos] for key, v in params.items()
                }
                col = int(np.where(task_cls == cls_idx)[0][0])
                estimators[col] = _make_fitted_binary(est, sl, meta)
        for col, cls_idx in enumerate(task_cls):
            if not degenerate[cls_idx]:
                continue
            warnings.warn(
                f"Label {self._col_label(col)} is present in "
                f"{'all' if counts[cls_idx] == n else 'no'} training "
                "examples."
            )
            cp = _ConstantPredictor()
            cp.y_ = np.array([1 if counts[cls_idx] == n else 0])
            estimators[col] = cp
        self.estimators_ = estimators
        self.estimator = clone(self.estimator)
        strip_runtime(self)
        return self

    # -- batched device path -------------------------------------------
    def _try_batched(self, backend, X, Y, sample_weight=None):
        est = self.estimator
        if not hasattr(type(est), "_build_fit_kernel"):
            return None
        # dict class_weight is keyed by original labels, which do not
        # map onto the {0,1} binary sub-problems -> generic path
        if isinstance(getattr(est, "class_weight", None), dict):
            return None
        from ..models.linear import _freeze, fit_would_pack, prepare_fit_X
        import jax
        import jax.numpy as jnp

        if prefers_host_engine(backend, est) and (
                not fit_would_pack(X, est)
                or getattr(est, "engine", None) == "host"):
            # the estimator resolves to its f64 host engine on this
            # host backend: the generic per-task path below runs that
            # engine, instead of the XLA-CPU batched program (shared
            # gate with search/eliminate — round-5 review). Packed
            # input has no host form and stays batched under 'auto';
            # an EXPLICIT engine='host' pin still routes to the host
            # per-task path. fit_would_pack is indptr-only, so the
            # bail costs nothing before prepare_fit_X's dense copy.
            return None
        try:
            # the BASELINE config-3 shape (hashed-text OvR): packable
            # sparse X ships packed and every class column's binary fit
            # runs the O(nnz) contractions on the one shared pair
            X_arr = prepare_fit_X(X, est)
        except Exception:
            return None
        n, d = X_arr.shape
        n_classes = Y.shape[1]

        # degenerate (single-valued) columns get ConstantPredictor on host
        col_sums = Y.sum(axis=0)
        degenerate = (col_sums == 0) | (col_sums == n)
        live = np.where(~degenerate)[0]

        X_dev, meta, aux = _binary_prep(est, X_arr)
        if meta is None:
            return None
        from ..models.linear import maybe_exact_matmuls

        static = _freeze(est._static_config(meta))
        fit_kernel = maybe_exact_matmuls(
            type(est), type(est)._build_fit_kernel(meta, static)
        )
        from ..models.linear import hyper_float

        hyper = {
            k: hyper_float(getattr(est, k)) for k in type(est)._hyper_names
        }
        max_negatives = self.max_negatives
        use_masks = max_negatives is not None

        def kernel(shared, task):
            y_bin = shared["Y"][:, task["cls"]]
            w = shared["sw"]
            if use_masks:
                # EXACT down-sampling: per-class keep masks are
                # precomputed on host with the same target arithmetic
                # and RandomState draw as the host path's
                # _negatives_mask (reference multiclass.py:76-106) and
                # ride the task axis — zero-weighting a row is
                # equivalent to dropping it for the weighted solvers.
                # (Replaces the round-2 Bernoulli approximation, whose
                # sampling semantics silently differed from the host
                # path of the same estimator.) Masks ship as uint8 and
                # widen on device.
                w = w * task["keep"].astype(jnp.float32)
            return fit_kernel(
                shared["X"], y_bin, w, shared["hyper"], shared["aux"]
            )

        shared = {
            "X": X_dev,
            "Y": jnp.asarray(Y),
            # the per-class kernels already weight by shared["sw"]: a
            # caller's full-length sample_weight drops straight in (the
            # keep masks compose with it multiplicatively below)
            "sw": (
                jnp.ones(n, jnp.float32) if sample_weight is None
                else jnp.asarray(sample_weight, jnp.float32)
            ),
            "hyper": {k: jnp.asarray(v) for k, v in hyper.items()},
            "aux": aux,
        }
        estimators = [None] * n_classes
        if live.size:
            from ..models.linear import _meta_signature
            from ..parallel import row_sharded_specs, structural_key

            # the per-fit closure is fully determined by (estimator
            # class, static config, meta signature, masking choice) —
            # the structural key lets repeated OvR fits reuse one
            # traced/compiled program despite the fresh closure
            kernel_key = structural_key(
                "ovr", type(est), static, _meta_signature(meta), use_masks
            )
            specs = row_sharded_specs(
                backend, shared, {"X": 0, "Y": 0, "sw": 0}
            )
            round_size = parse_partitions(self.partitions, int(live.size))
            # Down-sampling masks are (n_live, n)-shaped; at 1000-class
            # OvR on millions of rows co-materialising all of them on
            # host is TB-scale nonsense (round-3 VERDICT weak #7). The
            # masks for each dispatch span are built just-in-time, with
            # the span sized so one span's mask block stays inside the
            # host budget; per-class masks draw a fresh
            # RandomState(random_state), so spanning cannot change the
            # sampled sets.
            span_rows = (
                self._mask_span_rows(n) if use_masks else int(live.size)
            )
            if span_rows < int(live.size):
                # keep one round shape across the per-span dispatches
                # below (round-4 advisor: a shrunken tail round_size
                # meant an extra XLA compile for the final span): the
                # round never exceeds the memory-bounded span, and the
                # span is sized as a multiple of the round
                round_size = min(round_size, span_rows)
                span_rows -= span_rows % round_size
            spans = [
                (lo, min(lo + span_rows, int(live.size)))
                for lo in range(0, int(live.size), span_rows)
            ]
            # convergence-compacted path (the same backend entry point
            # the CV search uses): classes converge at different rates,
            # so the class-axis fan-out compacts exactly like a grid —
            # single-span only (the span machinery re-dispatches with a
            # pinned round shape the slice loop doesn't need)
            n_slice = (
                iterative_fit_supported(
                    backend, type(est), int(live.size),
                    getattr(est, "max_iter", None),
                )
                if len(spans) == 1 else None
            )
            parts = []
            if n_slice is not None:

                def derive(shared, task):
                    y_bin = shared["Y"][:, task["cls"]]
                    w = shared["sw"]
                    if use_masks:
                        w = w * task["keep"].astype(jnp.float32)
                    return (shared["X"], y_bin, w, shared["hyper"],
                            shared["aux"])

                iter_key = structural_key(
                    "ovr_iter", type(est), static, _meta_signature(meta),
                    use_masks, int(n_slice),
                )
                spec = _iterative_fit_spec(
                    type(est), meta, static, n_slice, derive, kernel,
                    kernel_key, iter_key,
                )
                task_args = {"cls": live.astype(np.int32)}
                if use_masks:
                    task_args["keep"] = self._exact_keep_masks(Y, live)
                parts.append(backend.batched_map_iterative(
                    spec, task_args, shared,
                    round_size=(
                        None if self.partitions in ("auto", None)
                        else round_size
                    ),
                    shared_specs=specs, cache_key=iter_key,
                ))
            else:
                for lo, hi in spans:
                    task_args = {"cls": live[lo:hi].astype(np.int32)}
                    if use_masks:
                        task_args["keep"] = self._exact_keep_masks(
                            Y, live[lo:hi]
                        )
                    parts.append(backend.batched_map(
                        kernel, task_args, shared, round_size=round_size,
                        shared_specs=specs, pad_to_round=len(spans) > 1,
                        cache_key=kernel_key,
                    ))
            stacked = parts[0] if len(parts) == 1 else (
                jax.tree_util.tree_map(
                    lambda *xs: np.concatenate(xs, axis=0), *parts
                )
            )
            from ..models.linear import annotate_round_kernel_mode

            annotate_round_kernel_mode(backend, meta)
            _warn_nonfinite_lanes(
                stacked,
                lambda i: f"class {self._col_label(live[i])!r}",
                "one-vs-rest",
            )
            for pos_idx, cls_idx in enumerate(live):
                params = jax.tree_util.tree_map(lambda a: a[pos_idx], stacked)
                estimators[cls_idx] = _make_fitted_binary(est, params, meta)
        for cls_idx in np.where(degenerate)[0]:
            warnings.warn(
                f"Label {self._col_label(cls_idx)} is present in "
                f"{'all' if col_sums[cls_idx] == n else 'no'} training examples."
            )
            cp = _ConstantPredictor()
            cp.y_ = np.array([1 if col_sums[cls_idx] == n else 0])
            estimators[cls_idx] = cp
        self.estimators_ = estimators
        return True

    def _mask_span_rows(self, n):
        """Class count per dispatch span so one span's (rows, n) uint8
        mask block fits in 1/8 of the host budget (several blocks can
        be alive at once: the span under construction plus blocks
        pinned by in-flight device transfers)."""
        from ..utils.meminfo import densify_budget_bytes

        budget, _ = densify_budget_bytes()
        if budget is None:
            return 1 << 30  # unknown budget: single span, as before
        return max(1, int(budget // 8) // max(int(n), 1))

    def _exact_keep_masks(self, Y, live):
        """(n_live, n) uint8 keep weights mirroring ``_negatives_mask``:
        per class, all positives kept plus an EXACT uniform
        without-replacement draw of the target number of negatives,
        from a fresh RandomState(random_state) per class — the same
        construction the host path performs per binary fit. uint8 (the
        kernel widens on device) keeps the block 4× smaller than f32;
        callers bound ``live`` via :meth:`_mask_span_rows` so the block
        never exceeds the host budget."""
        n = Y.shape[0]
        keep = np.ones((live.size, n), dtype=np.uint8)
        for i, cls in enumerate(live):
            y_bin = np.asarray(Y[:, cls])
            pos_mask = y_bin == 1
            n_pos = int(pos_mask.sum())
            n_neg = n - n_pos
            if self.method == "ratio":
                target = (
                    self.max_negatives
                    if isinstance(self.max_negatives, int)
                    else int(round(self.max_negatives * n_neg))
                )
            elif self.method == "multiplier":
                target = int(self.max_negatives * n_pos)
            else:
                raise ValueError(
                    "Unknown method. Options are 'ratio' or 'multiplier'."
                )
            if target >= n_neg:
                continue
            rng = np.random.RandomState(self.random_state)
            neg_idx = np.where(~pos_mask)[0]
            keep_neg = rng.choice(neg_idx, size=target, replace=False)
            mask = np.zeros(n, dtype=np.uint8)
            mask[pos_mask] = 1
            mask[keep_neg] = 1
            keep[i] = mask
        return keep

    def _col_label(self, col_idx):
        """Original class label for column ``col_idx`` of the (possibly
        binary-reduced) label matrix."""
        if getattr(self, "binary_", False):
            return self.classes_[col_idx + 1]
        return self.classes_[col_idx]

    # -- generic host path ---------------------------------------------
    def _fit_generic(self, backend, X, Y, fit_params):
        est = self.estimator

        def run_one(cls_idx):
            label = self._col_label(cls_idx)
            return _fit_binary(
                est, X, Y[:, cls_idx], fit_params,
                classes=[f"not-{label}", label],
                max_negatives=self.max_negatives,
                random_state=self.random_state, method=self.method,
            )

        self.estimators_ = backend.run_tasks(
            run_one, range(Y.shape[1]), verbose=self.verbose
        )

    # -- predict side ---------------------------------------------------
    def _per_class_scores(self, X, want_proba):
        check_is_fitted(self, "estimators_")
        cols = []
        for est in self.estimators_:
            if want_proba:
                cols.append(np.asarray(est.predict_proba(X))[:, 1])
            else:
                cols.append(_binary_confidence(est, X))
        return np.column_stack(cols)

    def _expanded_scores(self, X, want_proba):
        """Per-class score matrix over ``classes_`` — for the binary
        single-estimator case the negative column is the derived
        complement ([1-p, p] / [-s, s])."""
        scores = self._per_class_scores(X, want_proba)
        if getattr(self, "binary_", False):
            col = scores[:, 0]
            scores = (
                np.column_stack([1.0 - col, col]) if want_proba
                else np.column_stack([-col, col])
            )
        return scores

    def predict_proba(self, X):
        """Stacked per-class positive probabilities; optionally
        normalised (reference multiclass.py:337-362)."""
        scores = self._expanded_scores(X, want_proba=True)
        if self.norm:
            from sklearn.preprocessing import normalize

            scores = normalize(scores, norm=self.norm)
        return scores

    def decision_function(self, X):
        scores = self._per_class_scores(X, want_proba=False)
        if getattr(self, "binary_", False):
            # sklearn's binary OvR contract: 1-D confidences for the
            # positive class
            return scores[:, 0]
        return scores

    def predict(self, X):
        if self.multilabel_:
            proba_like = self._per_class_scores(
                X, want_proba=self._has_proba()
            )
            thresh = 0.5 if self._has_proba() else 0.0
            return (proba_like > thresh).astype(np.int32)
        scores = self._expanded_scores(X, want_proba=self._has_proba())
        return self.classes_[np.argmax(scores, axis=1)]

    def _has_proba(self):
        return all(hasattr(e, "predict_proba") for e in self.estimators_)

    @property
    def n_classes_(self):
        return len(self.classes_)


# ---------------------------------------------------------------------------
# OvO
# ---------------------------------------------------------------------------

class DistOneVsOneClassifier(BaseEstimator, ClassifierMixin):
    """One-vs-one with pair-axis fan-out (reference multiclass.py:365-475).

    Pairs (i, j), i<j; positive class is j (reference
    ``_fit_ovo_binary``, multiclass.py:155-172). The batched path masks
    rows by weight instead of slicing — the shape-dynamic part of the
    reference that XLA can't express directly.
    """

    def __init__(self, estimator, backend=None, partitions="auto",
                 n_jobs=None, verbose=0):
        self.estimator = estimator
        self.backend = backend
        self.partitions = partitions
        self.n_jobs = n_jobs
        self.verbose = verbose

    def fit(self, X, y=None, **fit_params):
        check_estimator_backend(self, self.verbose)
        from ..data import is_chunked

        backend = resolve_backend(self.backend, n_jobs=self.n_jobs)
        if is_chunked(X):
            return self._fit_streamed(backend, X, y, fit_params)
        if y is None:
            raise ValueError(
                "y is required for resident input (only ChunkedDataset "
                "input carries its own labels)"
            )
        y = np.asarray(y)
        self.classes_ = np.unique(y)
        k = len(self.classes_)
        self.pairs_ = [(i, j) for i in range(k) for j in range(i + 1, k)]

        done = None
        sw, sw_ok = full_length_sample_weight(fit_params, _n_rows(X))
        if sw_ok:
            done = self._try_batched(backend, X, y, sample_weight=sw)
        if done is None:
            self._fit_generic(backend, X, y, fit_params)
        self.estimator = clone(self.estimator)
        strip_runtime(self)
        return self

    # -- streamed out-of-core path --------------------------------------
    def _fit_streamed(self, backend, dataset, y, fit_params):
        """OvO over a ChunkedDataset: the PAIR axis rides the task axis
        of ONE streamed fit — every block is read once per solver pass
        for ALL ``k·(k-1)/2`` pairs, with pair membership composed on
        device as a weight mask (``in_pair × sample_weight``, the
        resident batched path's idiom) and labels binarised per task
        (positive class ``j``). No host fallback exists for out-of-core
        input, so unsupported configurations raise with the
        resident-path remedy."""
        import jax.numpy as jnp

        from ..models.linear import (
            _annotate_stream_meta, _freeze, hyper_float,
            prepare_sample_weight,
        )
        from ..models.streaming import stream_fit_tasks

        est = self.estimator
        est_cls = type(est)
        if getattr(est_cls, "_stream_fit_kind", None) is None:
            raise ValueError(
                f"{est_cls.__name__} has no streamed fit driver; "
                "ChunkedDataset OvO supports the linear families"
            )
        if getattr(est, "class_weight", None) is not None:
            raise ValueError(
                "class_weight does not map onto the streamed {0,1} "
                "binary sub-problems; fit with resident X for "
                "class-weighted OvO"
            )
        if getattr(est, "engine", None) == "host":
            raise ValueError(
                "engine='host' cannot fit a ChunkedDataset; use "
                "engine='auto'/'xla'"
            )
        if y is None:
            y = dataset.load_y()
        y = np.asarray(y)
        if y.ndim != 1 and not (y.ndim == 2 and y.shape[1] == 1):
            raise ValueError(
                "OvO needs 1-D multiclass labels; got y with shape "
                f"{y.shape}"
            )
        y = y.reshape(-1)
        sw, sw_ok = full_length_sample_weight(fit_params, dataset.n_rows)
        if not sw_ok:
            raise ValueError(
                "streamed OvO supports only a full-length sample_weight "
                f"fit param; got {sorted(fit_params)}"
            )
        if sw is None:
            sw = dataset.load_sw()
        self.classes_ = np.unique(y)
        k = len(self.classes_)
        self.pairs_ = [(i, j) for i in range(k) for j in range(i + 1, k)]
        y_idx = np.searchsorted(self.classes_, y).astype(np.int32)
        sw_arr = prepare_sample_weight(sw, dataset.n_rows)
        # binary sub-problem meta: classes {0, 1} exactly like the
        # resident batched path's _binary_prep
        meta = _annotate_stream_meta({
            "n_features": dataset.n_features,
            "classes": np.arange(2, dtype=np.int64),
            "n_classes": 2,
            "cw_arr": None,
        }, dataset)
        static = _freeze(est._static_config(meta))
        n_pairs = len(self.pairs_)
        hyper = {
            name: np.full(
                n_pairs, float(hyper_float(getattr(est, name))),
                np.float32,
            )
            for name in est_cls._hyper_names
        }
        if est_cls._stream_fit_kind == "gram" and "alpha" not in hyper:
            hyper["alpha"] = np.full(
                n_pairs, float(hyper_float(est.alpha)), np.float32
            )
        task_args = {
            "hyper": hyper,
            "i": np.asarray([p[0] for p in self.pairs_], np.int32),
            "j": np.asarray([p[1] for p in self.pairs_], np.int32),
        }

        def derive(block, task):
            yi = block["y"]
            in_pair = (yi == task["i"]) | (yi == task["j"])
            yb = (yi == task["j"]).astype(jnp.int32)
            # pair membership composes multiplicatively with the
            # caller's weights; block tail-padding rows carry zero
            # weight and fall out of every pair
            w = in_pair.astype(jnp.float32) * block["sw"]
            return block["X"], yb, w, task["hyper"]

        params = stream_fit_tasks(
            backend, est_cls, meta, static, dataset,
            {"y": y_idx, "sw": sw_arr}, task_args, derive=derive,
            key_extra=("ovo",),
        )
        _warn_nonfinite_lanes(
            params,
            lambda t: "pair (%r, %r)" % (
                self.classes_[self.pairs_[t][0]],
                self.classes_[self.pairs_[t][1]],
            ),
            "one-vs-one",
        )
        self.estimators_ = [
            _make_fitted_binary(
                est,
                {key: np.asarray(v)[t] for key, v in params.items()},
                meta,
            )
            for t in range(n_pairs)
        ]
        self.estimator = clone(self.estimator)
        strip_runtime(self)
        return self

    def _try_batched(self, backend, X, y, sample_weight=None):
        est = self.estimator
        if not hasattr(type(est), "_build_fit_kernel"):
            return None
        # dict class_weight is keyed by original labels, which do not
        # map onto the {0,1} binary sub-problems -> generic path
        if isinstance(getattr(est, "class_weight", None), dict):
            return None
        from ..models.linear import _freeze, fit_would_pack, prepare_fit_X
        import jax
        import jax.numpy as jnp

        if prefers_host_engine(backend, est) and (
                not fit_would_pack(X, est)
                or getattr(est, "engine", None) == "host"):
            # the estimator resolves to its f64 host engine on this
            # host backend: the generic per-task path below runs that
            # engine, instead of the XLA-CPU batched program (shared
            # gate with search/eliminate — round-5 review). Packed
            # input has no host form and stays batched under 'auto';
            # an EXPLICIT engine='host' pin still routes to the host
            # per-task path. fit_would_pack is indptr-only, so the
            # bail costs nothing before prepare_fit_X's dense copy.
            return None
        try:
            X_arr = prepare_fit_X(X, est)
        except Exception:
            return None
        y_idx = np.searchsorted(self.classes_, y).astype(np.int32)
        X_dev, meta, aux = _binary_prep(est, X_arr)
        if meta is None:
            return None
        from ..models.linear import maybe_exact_matmuls

        static = _freeze(est._static_config(meta))
        fit_kernel = maybe_exact_matmuls(
            type(est), type(est)._build_fit_kernel(meta, static)
        )
        from ..models.linear import hyper_float

        hyper = {
            k_: hyper_float(getattr(est, k_))
            for k_ in type(est)._hyper_names
        }

        def kernel(shared, task):
            yi = shared["y"]
            in_pair = (yi == task["i"]) | (yi == task["j"])
            y_bin = (yi == task["j"]).astype(jnp.int32)
            # pair membership composes multiplicatively with the
            # caller's per-sample weights (ones when absent), mirroring
            # search.py's fold-mask x sample_weight contract
            w = in_pair.astype(jnp.float32) * shared["sw"]
            return fit_kernel(
                shared["X"], y_bin, w, shared["hyper"], shared["aux"]
            )

        n = X_arr.shape[0]
        shared = {
            "X": X_dev,
            "y": jnp.asarray(y_idx),
            "sw": (
                jnp.ones(n, jnp.float32) if sample_weight is None
                else jnp.asarray(sample_weight, jnp.float32)
            ),
            "hyper": {k_: jnp.asarray(v) for k_, v in hyper.items()},
            "aux": aux,
        }
        task_args = {
            "i": np.asarray([p[0] for p in self.pairs_], dtype=np.int32),
            "j": np.asarray([p[1] for p in self.pairs_], dtype=np.int32),
        }
        from ..models.linear import _meta_signature
        from ..parallel import row_sharded_specs, structural_key

        specs = row_sharded_specs(
            backend, shared, {"X": 0, "y": 0, "sw": 0}
        )
        kernel_key = structural_key(
            "ovo", type(est), static, _meta_signature(meta)
        )
        # convergence-compacted path: class pairs converge at different
        # rates (same backend entry point as the CV search / OvR)
        n_slice = iterative_fit_supported(
            backend, type(est), len(self.pairs_),
            getattr(est, "max_iter", None),
        )
        if n_slice is not None:

            def derive(shared, task):
                yi = shared["y"]
                in_pair = (yi == task["i"]) | (yi == task["j"])
                y_bin = (yi == task["j"]).astype(jnp.int32)
                w = in_pair.astype(jnp.float32) * shared["sw"]
                return shared["X"], y_bin, w, shared["hyper"], shared["aux"]

            iter_key = structural_key(
                "ovo_iter", type(est), static, _meta_signature(meta),
                int(n_slice),
            )
            spec = _iterative_fit_spec(
                type(est), meta, static, n_slice, derive, kernel,
                kernel_key, iter_key,
            )
            stacked = backend.batched_map_iterative(
                spec, task_args, shared,
                round_size=(
                    None if self.partitions in ("auto", None)
                    else parse_partitions(self.partitions, len(self.pairs_))
                ),
                shared_specs=specs, cache_key=iter_key,
            )
        else:
            stacked = backend.batched_map(
                kernel, task_args, shared,
                round_size=parse_partitions(
                    self.partitions, len(self.pairs_)
                ),
                shared_specs=specs,
                cache_key=kernel_key,
            )
        from ..models.linear import annotate_round_kernel_mode

        annotate_round_kernel_mode(backend, meta)
        _warn_nonfinite_lanes(
            stacked,
            lambda t: "pair (%r, %r)" % (
                self.classes_[self.pairs_[t][0]],
                self.classes_[self.pairs_[t][1]],
            ),
            "one-vs-one",
        )
        self.estimators_ = [
            _make_fitted_binary(
                est, jax.tree_util.tree_map(lambda a: a[t], stacked), meta
            )
            for t in range(len(self.pairs_))
        ]
        return True

    def _fit_generic(self, backend, X, y, fit_params):
        est = self.estimator
        y_idx = np.searchsorted(self.classes_, y)
        n = _n_rows(X)

        def run_one(pair):
            i, j = pair
            cond = (y_idx == i) | (y_idx == j)
            idx = np.where(cond)[0]
            X_sub, _ = safe_split(est, X, None, idx)
            y_bin = (y_idx[idx] == j).astype(np.int32)
            fp = fit_params
            sw = fp.get("sample_weight") if fp else None
            if sw is not None:
                sw_arr = np.asarray(sw)
                if sw_arr.ndim == 2 and sw_arr.shape[1] == 1:
                    # flatten (n, 1) columns BEFORE slicing, like the
                    # shared device-path contract — a sliced (k, 1)
                    # weight would fail sklearn's 1-D validation
                    sw_arr = sw_arr.ravel()
                if sw_arr.shape[:1] == (n,):
                    # full-length per-sample weights follow the pair's
                    # row subset (the host mirror of the device path's
                    # membership-mask x sample_weight composition;
                    # passing them unsliced would length-mismatch the
                    # sliced X)
                    fp = dict(fp, sample_weight=sw_arr[idx])
            return _fit_binary(est, X_sub, y_bin, fp, classes=[i, j])

        self.estimators_ = backend.run_tasks(
            run_one, self.pairs_, verbose=self.verbose
        )

    def decision_function(self, X):
        """sklearn-style OvO aggregation: votes plus a bounded
        sum-of-confidences tie-break."""
        check_is_fitted(self, "estimators_")
        n = _n_rows(X)
        k = len(self.classes_)
        votes = np.zeros((n, k))
        sum_conf = np.zeros((n, k))
        for (i, j), est in zip(self.pairs_, self.estimators_):
            conf = _binary_confidence(est, X).reshape(n)
            votes[:, i] += conf < 0
            votes[:, j] += conf >= 0
            sum_conf[:, i] -= conf
            sum_conf[:, j] += conf
        return votes + sum_conf / (3 * (np.abs(sum_conf) + 1))

    def predict(self, X):
        return self.classes_[np.argmax(self.decision_function(X), axis=1)]

    @property
    def n_classes_(self):
        return len(self.classes_)
