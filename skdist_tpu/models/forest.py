"""
Forest kernels: RandomForest / ExtraTrees (classifier + regressor) and
RandomTreesEmbedding.

Where the reference ships one Spark task per tree — broadcast the data,
``sc.parallelize(seeds).map(_build_trees).collect()`` the fitted Cython
trees back (``/root/reference/skdist/distribute/ensemble.py:278-325``) —
here the tree axis is the vmapped task axis of ONE histogram-tree
program (``models/tree.py``): per-tree PRNG seeds ride the task axis,
bootstrap resampling is a scatter-add count vector times the sample
weights (the reference's ``_generate_sample_indices`` + bincount,
ensemble.py:51-55,88-104, done on device), and the fitted forest is a
stacked pytree of tree arrays living in host memory. The distributed
wrappers (``distribute/ensemble.py``) shard the same axis over the TPU
mesh via ``backend.batched_map``.
"""

from functools import lru_cache

import numpy as np
import jax
import jax.numpy as jnp

from ..base import BaseEstimator, ClassifierMixin, RegressorMixin, TransformerMixin
from ..ops.binning import apply_bins, quantile_bin_edges
from ..parallel import LocalBackend
from .linear import (
    as_dense_f32,
    class_weight_vector,
    encode_labels,
    prepare_sample_weight,
)
from .tree import (
    DecisionTreeClassifier,
    DecisionTreeRegressor,
    build_tree_kernel,
    classification_channels,
    feature_importances_from_tree,
    matmul_lane_cap,
    n_tree_nodes,
    regression_channels,
    resolve_hist_config,
    resolve_max_features,
    tree_predict_kernel,
)

__all__ = [
    "RandomForestClassifier",
    "RandomForestRegressor",
    "ExtraTreesClassifier",
    "ExtraTreesRegressor",
    "RandomTreesEmbedding",
]

MAX_RAND_SEED = np.iinfo(np.int32).max

# module-level cache of jitted forest walkers: jax.jit caches on function
# identity, so per-call closures would recompile on every predict
_WALKER_CACHE = {}


def _forest_walker(max_depth, mode):
    key = (max_depth, mode)
    fn = _WALKER_CACHE.get(key)
    if fn is None:
        walk = tree_predict_kernel(max_depth, return_nodes=(mode == "apply"))

        if mode == "apply":
            @jax.jit
            def fn(trees, Xb):
                return jax.vmap(lambda t: walk(t, Xb))(trees).T  # (n, T)
        else:
            @jax.jit
            def fn(trees, Xb):
                per_tree = jax.vmap(lambda t: walk(t, Xb))(trees)  # (T,n,K)
                return jnp.mean(per_tree, axis=0)

        _WALKER_CACHE[key] = fn
    return fn


def _bootstrap_counts(seed, n, dtype=jnp.float32):
    """Reproduce a tree's bootstrap draw from its seed (the same draw
    the fit kernel made), so OOB masks never need to be persisted."""
    kboot, _ = jax.random.split(jax.random.PRNGKey(seed))
    idx = jax.random.randint(kboot, (n,), 0, n)
    return jnp.zeros((n,), dtype).at[idx].add(1.0)


@lru_cache(maxsize=8)
def _bootstrap_counts_batch(n):
    """Jitted (seeds,) -> (T, n) bootstrap counts; cached per n so
    repeat host-engine fits skip re-tracing (~2 s per fit otherwise)."""
    return jax.jit(jax.vmap(lambda s: _bootstrap_counts(s, n)))


def _oob_aggregator(max_depth):
    """Cached jitted OOB aggregation (same function-identity caching
    rationale as _forest_walker). Masks are regenerated from the stored
    per-tree seeds, so warm-started trees participate too."""
    key = (max_depth, "oob")
    fn = _WALKER_CACHE.get(key)
    if fn is None:
        walk = tree_predict_kernel(max_depth)

        @jax.jit
        def fn(trees, seeds, Xb):
            n = Xb.shape[0]
            per_tree = jax.vmap(lambda t: walk(t, Xb))(trees)  # (T, n, K)
            counts = jax.vmap(lambda s: _bootstrap_counts(s, n))(seeds)
            m = (counts == 0).astype(per_tree.dtype)  # (T, n)
            num = jnp.sum(per_tree * m[:, :, None], axis=0)
            cnt = jnp.sum(m, axis=0)
            return num / jnp.maximum(cnt, 1.0)[:, None], cnt

        _WALKER_CACHE[key] = fn
    return fn


def make_forest_tree_kernel(d, n_bins, channels, max_depth, max_features,
                            min_samples_split, min_samples_leaf,
                            min_impurity_decrease, extra, classification,
                            bootstrap, hist_mode="auto", hist_block=None,
                            fractional_weights=False):
    """One-tree task kernel for ``backend.batched_map``: the task is a
    scalar PRNG seed (mirroring the reference's per-tree random states,
    ensemble.py:278). The seed is stored with the tree so OOB masks
    (``_oob_aggregator``) regenerate the bootstrap draw on demand.

    The kernel is MEMOISED on its full static config: ``_jit_vmapped``'s
    compile cache keys on kernel identity, so handing back the same
    closure for the same config is what lets a warm refit (or the next
    forest in a grid) skip XLA compilation entirely — a fresh closure
    per fit silently recompiled every forest. ``hist_mode="auto"`` is
    resolved to a concrete (mode, block) BEFORE the memo key, so a
    recalibration (the on-chip sweep writes one mid-process) still
    takes effect on the next fit."""
    # allow_native=False: this kernel IS the XLA path — forest.fit
    # routes native-mode fits to the host engine before reaching here
    hist_mode, hist_block = resolve_hist_config(
        d, n_bins, hist_mode, hist_block, allow_native=False,
        fractional_weights=fractional_weights,
    )
    return _forest_kernel_cached(
        d, n_bins, channels, max_depth, max_features, min_samples_split,
        min_samples_leaf, min_impurity_decrease, extra, classification,
        bootstrap, hist_mode, hist_block,
    )


@lru_cache(maxsize=64)
def _forest_kernel_cached(d, n_bins, channels, max_depth, max_features,
                          min_samples_split, min_samples_leaf,
                          min_impurity_decrease, extra, classification,
                          bootstrap, hist_mode, hist_block):
    grow = build_tree_kernel(
        n_features=d, n_bins=n_bins, channels=channels, max_depth=max_depth,
        max_features=max_features, min_samples_split=min_samples_split,
        min_samples_leaf=min_samples_leaf,
        min_impurity_decrease=min_impurity_decrease, extra=extra,
        classification=classification, hist_mode=hist_mode,
    )
    K = channels - 1 if classification else 1

    def kernel(shared, task):
        Xb, y, sw = shared["Xb"], shared["y"], shared["sw"]
        n = Xb.shape[0]
        key = jax.random.PRNGKey(task["seed"])
        _, kgrow = jax.random.split(key)
        w = sw
        if bootstrap:
            w = sw * _bootstrap_counts(task["seed"], n, sw.dtype)
        if classification:
            Ych = classification_channels(y, w, K)
        else:
            Ych = regression_channels(y, w)
        tree = grow(Xb, Ych, kgrow)
        # the seed travels with the tree: OOB masks and bootstrap draws
        # are reproducible from it (nothing O(n) is persisted)
        tree["seed"] = task["seed"]
        return tree

    # structural compile-cache key: the closure is fully determined by
    # this memo's own (fully-resolved) argument tuple; the batched_map
    # call site passes it so the jit/AOT caches survive an lru_cache
    # eviction of the closure itself
    from ..parallel import structural_key

    kernel.cache_key = structural_key(
        "forest_tree", "tree_kernel", d, n_bins, channels, max_depth,
        max_features, min_samples_split, min_samples_leaf,
        min_impurity_decrease, extra, classification, bootstrap,
        hist_mode, hist_block,
    )
    #: the RESOLVED engine this kernel runs (fit sizes its rounds by it)
    kernel.hist_mode = hist_mode
    return kernel


# Two SEPARATE memos, same identity + weakref-validation scheme as the
# backend's broadcast cache (a recycled id() can never serve stale
# entries; collecting X evicts them):
#   _EDGE_MEMO: (id(X), n_bins) -> (weakref(X), quantile edges) —
#       written ONLY by _memo_edges, so it only ever holds edges that
#       are quantile_bin_edges(X) for that exact X.
#   _XB_MEMO:   (id(X), n_bins) -> (weakref(X), edges, Xb) — written
#       by _memo_apply_bins with WHATEVER edges the caller passed
#       (a warm_start refit legitimately applies inherited edges).
# Keeping them separate closes the poisoning path where a warm-start
# apply on a new X wrote its inherited edges where _memo_edges would
# later serve them as X's own quantile edges, silently changing the
# trees a subsequent fresh fit grows.
_EDGE_MEMO = {}
_XB_MEMO = {}
_BIN_MEMO_MAX = 4


def _memo_lookup(memo, X, n_bins, enabled):
    if not enabled or not isinstance(X, np.ndarray):
        return None, None
    key = (id(X), int(n_bins))
    ent = memo.get(key)
    if ent is not None:
        if ent[0]() is X:
            return key, ent
        memo.pop(key, None)
    return key, None


def _memo_store(memo, key, X, *values):
    import weakref

    memo[key] = (weakref.ref(X, lambda _r: memo.pop(key, None)), *values)
    while len(memo) > _BIN_MEMO_MAX:
        try:
            memo.pop(next(iter(memo)))
        except (KeyError, StopIteration):
            break


def _memo_edges(X, n_bins, enabled):
    key, ent = _memo_lookup(_EDGE_MEMO, X, n_bins, enabled)
    if ent is not None:
        return ent[1]
    edges = quantile_bin_edges(X, n_bins)
    if key is not None:
        _memo_store(_EDGE_MEMO, key, X, np.asarray(edges))
    return edges


def _memo_apply_bins(X, edges, n_bins, enabled):
    key, ent = _memo_lookup(_XB_MEMO, X, n_bins, enabled)
    if ent is not None and np.array_equal(ent[1], edges):
        return ent[2]
    Xb = np.asarray(apply_bins(jnp.asarray(X), jnp.asarray(edges)))
    if key is not None:
        _memo_store(_XB_MEMO, key, X, np.asarray(edges), Xb)
    return Xb


class _BaseForest(BaseEstimator):
    """Shared forest machinery; subclasses set ``_extra`` (random
    thresholds) and classification/regression via mixins.

    ``warm_start=True`` keeps previously grown trees and appends
    ``n_estimators - len(grown)`` new ones (reference ensemble.py:250-272).
    """

    _extra = False

    def __init__(self, n_estimators=100, max_depth=8, n_bins=32,
                 max_features="sqrt", min_samples_split=2, min_samples_leaf=1,
                 min_impurity_decrease=0.0, bootstrap=True, oob_score=False,
                 class_weight=None, warm_start=False, random_state=None,
                 n_jobs=None, hist_mode="auto"):
        self.n_estimators = n_estimators
        self.max_depth = max_depth
        self.n_bins = n_bins
        self.max_features = max_features
        self.min_samples_split = min_samples_split
        self.min_samples_leaf = min_samples_leaf
        self.min_impurity_decrease = min_impurity_decrease
        self.bootstrap = bootstrap
        self.oob_score = oob_score
        self.class_weight = class_weight
        self.warm_start = warm_start
        self.random_state = random_state
        self.n_jobs = n_jobs
        self.hist_mode = hist_mode

    @property
    def _classification(self):
        return isinstance(self, ClassifierMixin)

    # distributed wrappers override to route through their backend
    def _resolve_fit_backend(self):
        return LocalBackend(n_jobs=self.n_jobs), None

    def fit(self, X, y, sample_weight=None):
        X = as_dense_f32(X)
        n, d = X.shape
        sw = prepare_sample_weight(sample_weight, n)
        backend, round_size = self._resolve_fit_backend()
        # binning is a pure function of (X, n_bins); under the backend's
        # reuse_broadcast contract (mutating X after handing it over is
        # user error, as with a Spark broadcast) repeat fits on the same
        # host X skip both the quantile pass and the bin-apply transfer
        # — and the memoised Xb's stable identity is what lets the
        # broadcast cache hit on the placement below.
        reuse = getattr(backend, "reuse_broadcast", False)
        warm = self.warm_start and getattr(self, "_trees", None) is not None
        if warm:
            # existing trees' thresholds are bin ids under the original
            # edges — a warm refit must keep binning consistent
            edges = self._edges
        else:
            edges = _memo_edges(X, self.n_bins, reuse)

        if self._classification:
            y_enc, classes = encode_labels(y)
            self.classes_ = classes
            K = len(classes)
            channels = K + 1
            cw = getattr(self, "class_weight", None)
            if cw is not None:
                if cw == "balanced":
                    counts = np.bincount(y_enc, minlength=K).astype(np.float64)
                    per_class = len(y_enc) / (K * np.maximum(counts, 1))
                elif isinstance(cw, dict):
                    per_class = class_weight_vector(cw, classes)
                else:
                    raise ValueError(
                        f"Unsupported class_weight {cw!r}: use 'balanced' "
                        "or a {label: weight} dict"
                    )
                sw = sw * per_class[y_enc].astype(np.float32)
        else:
            y_enc = np.asarray(y, dtype=np.float32)
            K = 1
            channels = 4
        if self.oob_score and not self.bootstrap:
            raise ValueError("oob_score requires bootstrap=True")

        prev = getattr(self, "_trees", None) if warm else None
        n_prev = 0
        if prev is not None:
            n_prev = int(prev["feat"].shape[0])
        n_more = self.n_estimators - n_prev
        if n_more < 0:
            raise ValueError(
                f"warm_start: n_estimators={self.n_estimators} is smaller "
                f"than the {n_prev} trees already grown"
            )

        if n_more > 0:
            rng = np.random.RandomState(self.random_state)
            if n_prev:  # advance the stream past already-drawn seeds
                rng.randint(MAX_RAND_SEED, size=n_prev)
            seeds = rng.randint(MAX_RAND_SEED, size=n_more).astype(np.int32)
            Xb = _memo_apply_bins(X, edges, self.n_bins, reuse)
            mode, _ = resolve_hist_config(
                d, self.n_bins, getattr(self, "hist_mode", "auto")
            )
            # explicit opt-in that can't be honored on this host raises
            # (shared diagnosis with tree.py); the distributed-backend
            # case raises from resolve_hist_config(allow_native=False)
            # inside make_forest_tree_kernel instead
            from .native_forest import native_supported_or_raise

            use_native = (
                mode == "native"
                and isinstance(backend, LocalBackend)
                and native_supported_or_raise(
                    self.n_bins,
                    getattr(self, "hist_mode", "auto") == "native",
                )
            )
            if use_native:
                new_trees = self._fit_native(Xb, y_enc, sw, seeds, d)
            else:
                kernel = make_forest_tree_kernel(
                    d=d, n_bins=self.n_bins, channels=channels,
                    max_depth=self.max_depth,
                    max_features=resolve_max_features(self.max_features, d),
                    min_samples_split=self.min_samples_split,
                    min_samples_leaf=self.min_samples_leaf,
                    min_impurity_decrease=self.min_impurity_decrease,
                    extra=self._extra, classification=self._classification,
                    bootstrap=self.bootstrap,
                    hist_mode=getattr(self, "hist_mode", "auto"),
                    # sw already folds class_weight in, so one integral
                    # check covers both fractional sources; only a
                    # calibrated matmul_sib 'auto' pick consults this
                    fractional_weights=bool(
                        np.any(np.asarray(sw) != np.rint(sw))
                    ),
                )
                if kernel.hist_mode in ("matmul", "matmul_sib"):
                    # the engine's one-hot right factor grows with the
                    # round: bound the lanes a device grows at once
                    # (tree.matmul_lane_cap says why)
                    round_size = min(
                        round_size or n_more,
                        matmul_lane_cap(n, self.max_depth, channels)
                        * max(1, getattr(backend, "n_task_slots", 1)),
                    )
                shared = {
                    "Xb": Xb,  # host-staged: batched_map places (and can
                    "y": np.asarray(y_enc),  # cache) the sharded replicas
                    "sw": np.asarray(sw),
                }
                new_trees = backend.batched_map(
                    kernel, {"seed": seeds}, shared, round_size=round_size,
                    cache_key=kernel.cache_key,
                )
            if prev is not None:
                self._trees = jax.tree_util.tree_map(
                    lambda a, b: np.concatenate([a, b], axis=0), prev, new_trees
                )
            else:
                self._trees = new_trees
        self._edges = edges
        self.n_features_in_ = d
        if self.oob_score:
            self._compute_oob(X, y_enc)
        return self

    def _fit_native(self, Xb, y_enc, sw, seeds, d):
        """Grow trees with the host engine (models/native_forest.py):
        same histogram algorithm, per-level accumulation in the
        multithreaded C kernel instead of an XLA scatter, zero compile
        time. Bootstrap weights reproduce the device path's
        ``_bootstrap_counts`` draw exactly — OOB scoring regenerates
        masks from the stored seeds through that one function, so both
        engines must agree on what each seed drew."""
        from .native_forest import grow_forest_native

        n = Xb.shape[0]
        sw = np.asarray(sw, np.float32)
        bootstrap = self.bootstrap

        def weights(t0, t1):
            # per-chunk: a 500-tree x 1M-row fit must not materialise
            # the full (T, n) weight matrix the engine's budget
            # chunking exists to avoid
            if bootstrap:
                counts = np.asarray(
                    _bootstrap_counts_batch(n)(jnp.asarray(seeds[t0:t1]))
                )
                return sw[None, :] * counts
            return np.broadcast_to(sw, (t1 - t0, n)).copy()

        n_jobs = self.n_jobs
        # joblib convention: None -> default, negative -> all cores
        # (LocalBackend treats the same attribute this way; the C
        # kernel would clamp a raw -1 to ONE thread)
        n_threads = None if n_jobs is None or n_jobs < 1 else int(n_jobs)
        return grow_forest_native(
            Xb, y_enc, weights, seeds,
            n_bins=self.n_bins, max_depth=self.max_depth,
            max_features=resolve_max_features(self.max_features, d),
            min_samples_split=self.min_samples_split,
            min_samples_leaf=self.min_samples_leaf,
            min_impurity_decrease=self.min_impurity_decrease,
            extra=self._extra, classification=self._classification,
            n_classes=len(getattr(self, "classes_", ())) or 1,
            n_threads=n_threads,
        )

    def _compute_oob(self, X, y_enc):
        """Real out-of-bag scoring (the reference stubbed this,
        ensemble.py:338-340): each sample is scored by the trees whose
        bootstrap missed it. The per-tree masks are consumed here and
        stripped from the fitted trees — they index the training rows
        and must not survive into predict/pickle/warm-start."""
        nodes = self._native_walk(X, "apply")
        if nodes is not None:
            # host path: per-tree leaf gather + mask, no XLA walker
            # compile; ONLY the bootstrap-draw regeneration stays on
            # jax (PRNG parity with the device path is the contract)
            n, T = nodes.shape
            leaf = np.asarray(self._trees["leaf"])  # (T, N, K)
            seeds = np.asarray(self._trees["seed"])
            num = np.zeros((n, leaf.shape[2]), np.float32)
            cnt = np.zeros(n, np.float32)
            # seeds in chunks: the counts matrix stays (16, n)-sized,
            # honouring the same no-(T, n)-materialisation contract as
            # _fit_native's weights() callback
            ch = 16
            for t0 in range(0, T, ch):
                counts = np.asarray(_bootstrap_counts_batch(n)(
                    jnp.asarray(seeds[t0:t0 + ch])
                ))
                for i in range(counts.shape[0]):
                    t = t0 + i
                    m = counts[i] == 0
                    num[m] += leaf[t, nodes[m, t]]
                    cnt += m
            agg = num / np.maximum(cnt, 1.0)[:, None]
        else:
            trees = jax.tree_util.tree_map(jnp.asarray, self._trees)
            Xb = apply_bins(jnp.asarray(X), jnp.asarray(self._edges))
            oob_agg = _oob_aggregator(self.max_depth)
            agg, cnt = jax.device_get(
                oob_agg(trees, trees["seed"], Xb)
            )
        covered = np.asarray(cnt) > 0
        if not covered.all():
            import warnings

            warnings.warn(
                "Some samples were in-bag for every tree; OOB estimates "
                "for them are undefined and excluded from oob_score_."
            )
        if self._classification:
            self.oob_decision_function_ = agg
            pred = np.argmax(agg, axis=1)
            self.oob_score_ = float(
                np.mean(pred[covered] == np.asarray(y_enc)[covered])
            ) if covered.any() else float("nan")
        else:
            self.oob_prediction_ = agg[:, 0]
            yv = np.asarray(y_enc)[covered]
            pv = agg[covered, 0]
            ss_res = float(np.sum((yv - pv) ** 2))
            ss_tot = float(np.sum((yv - yv.mean()) ** 2))
            self.oob_score_ = (
                1.0 - ss_res / ss_tot if ss_tot > 0 else float("nan")
            )

    # ------------------------------------------------------------------
    def _check_fitted(self):
        if not hasattr(self, "_trees"):
            raise AttributeError(
                f"This {type(self).__name__} instance is not fitted yet."
            )

    def _native_walk(self, X, mode):
        """Host C walker (native/hist_tree.c::forest_walk): on a
        CPU-backed process the predict side, like the native fit,
        needs no XLA compile at all. Returns None to fall through to
        the XLA walker (accelerator platforms, C kernel unavailable)."""
        if jax.default_backend() != "cpu":
            return None
        from ..native import forest_walk_native, hist_tree_available
        from ..ops.binning import apply_bins_np

        # availability first (binning a big X only to discard it on a
        # compiler-less host would tax every predict); the width check
        # falls through so the XLA path raises its usual loud shape
        # error instead of the C walker reading past Xb
        if not hist_tree_available() or X.shape[1] != len(self._edges):
            return None
        n_jobs = getattr(self, "n_jobs", None)
        return forest_walk_native(
            apply_bins_np(X, self._edges), self._trees, self.max_depth,
            mode=mode,
            n_threads=None if n_jobs is None or n_jobs < 1 else int(n_jobs),
        )

    def _forest_values(self, X):
        """Mean over trees of per-tree leaf outputs → (n, K_out)."""
        self._check_fitted()
        X = as_dense_f32(X)
        out = self._native_walk(X, "predict")
        if out is not None:
            return out
        fn = _forest_walker(self.max_depth, "predict")
        trees = jax.tree_util.tree_map(jnp.asarray, self._trees)
        Xb = apply_bins(jnp.asarray(X), jnp.asarray(self._edges))
        return np.asarray(fn(trees, Xb))

    def apply(self, X):
        """(n, n_estimators) leaf ids — sklearn ``forest.apply``."""
        self._check_fitted()
        X = as_dense_f32(X)
        out = self._native_walk(X, "apply")
        if out is not None:
            return out
        fn = _forest_walker(self.max_depth, "apply")
        trees = jax.tree_util.tree_map(jnp.asarray, self._trees)
        Xb = apply_bins(jnp.asarray(X), jnp.asarray(self._edges))
        return np.asarray(fn(trees, Xb))

    @property
    def feature_importances_(self):
        self._check_fitted()
        T = self._trees["feat"].shape[0]
        imps = np.stack([
            feature_importances_from_tree(
                self._trees["feat"][t], self._trees["gain"][t],
                self.n_features_in_,
            )
            for t in range(T)
        ])
        return imps.mean(axis=0)

    @property
    def estimators_(self):
        """Per-tree estimator views (reference parity: fitted trees are
        collected into ``estimators_``, ensemble.py:325)."""
        self._check_fitted()
        cls = (
            DecisionTreeClassifier if self._classification
            else DecisionTreeRegressor
        )
        out = []
        T = self._trees["feat"].shape[0]
        for t in range(T):
            est = cls(
                max_depth=self.max_depth, n_bins=self.n_bins,
                max_features=self.max_features,
                min_samples_split=self.min_samples_split,
                min_samples_leaf=self.min_samples_leaf,
                min_impurity_decrease=self.min_impurity_decrease,
                splitter="random" if self._extra else "best",
            )
            est._params = jax.tree_util.tree_map(
                lambda a: np.asarray(a[t]), self._trees
            )
            est._params["edges"] = np.asarray(self._edges)
            est._meta = {"n_features": self.n_features_in_}
            est.n_features_in_ = self.n_features_in_
            if self._classification:
                est.classes_ = self.classes_
                est._meta.update(
                    classes=self.classes_, n_classes=len(self.classes_)
                )
            out.append(est)
        return out


class _ForestClassifierMixin(ClassifierMixin):
    def predict_proba(self, X):
        return self._forest_values(X)

    def predict_log_proba(self, X):
        return np.log(np.clip(self.predict_proba(X), 1e-15, None))

    def predict(self, X):
        return self.classes_[np.argmax(self.predict_proba(X), axis=1)]


class _ForestRegressorMixin(RegressorMixin):
    def predict(self, X):
        out = self._forest_values(X)
        return out[:, 0] if out.ndim == 2 and out.shape[1] == 1 else out


class RandomForestClassifier(_BaseForest, _ForestClassifierMixin):
    """Histogram random forest (bagged best-split trees)."""


class RandomForestRegressor(_BaseForest, _ForestRegressorMixin):
    def __init__(self, n_estimators=100, max_depth=8, n_bins=32,
                 max_features=1.0, min_samples_split=2, min_samples_leaf=1,
                 min_impurity_decrease=0.0, bootstrap=True, oob_score=False,
                 warm_start=False, random_state=None, n_jobs=None,
                 hist_mode="auto"):
        super().__init__(
            n_estimators=n_estimators, max_depth=max_depth, n_bins=n_bins,
            max_features=max_features, min_samples_split=min_samples_split,
            min_samples_leaf=min_samples_leaf,
            min_impurity_decrease=min_impurity_decrease, bootstrap=bootstrap,
            oob_score=oob_score, warm_start=warm_start,
            random_state=random_state, n_jobs=n_jobs, hist_mode=hist_mode,
        )


class ExtraTreesClassifier(_BaseForest, _ForestClassifierMixin):
    """Extremely randomised trees: random per-(node, feature) thresholds,
    no bootstrap by default (sklearn semantics)."""

    _extra = True

    def __init__(self, n_estimators=100, max_depth=8, n_bins=32,
                 max_features="sqrt", min_samples_split=2, min_samples_leaf=1,
                 min_impurity_decrease=0.0, bootstrap=False, oob_score=False,
                 class_weight=None, warm_start=False, random_state=None,
                 n_jobs=None, hist_mode="auto"):
        super().__init__(
            n_estimators=n_estimators, max_depth=max_depth, n_bins=n_bins,
            max_features=max_features, min_samples_split=min_samples_split,
            min_samples_leaf=min_samples_leaf,
            min_impurity_decrease=min_impurity_decrease, bootstrap=bootstrap,
            oob_score=oob_score, class_weight=class_weight,
            warm_start=warm_start, random_state=random_state, n_jobs=n_jobs,
            hist_mode=hist_mode,
        )


class ExtraTreesRegressor(_BaseForest, _ForestRegressorMixin):
    _extra = True

    def __init__(self, n_estimators=100, max_depth=8, n_bins=32,
                 max_features=1.0, min_samples_split=2, min_samples_leaf=1,
                 min_impurity_decrease=0.0, bootstrap=False, oob_score=False,
                 warm_start=False, random_state=None, n_jobs=None,
                 hist_mode="auto"):
        super().__init__(
            n_estimators=n_estimators, max_depth=max_depth, n_bins=n_bins,
            max_features=max_features, min_samples_split=min_samples_split,
            min_samples_leaf=min_samples_leaf,
            min_impurity_decrease=min_impurity_decrease, bootstrap=bootstrap,
            oob_score=oob_score, warm_start=warm_start,
            random_state=random_state, n_jobs=n_jobs, hist_mode=hist_mode,
        )


class RandomTreesEmbedding(_BaseForest, TransformerMixin):
    """Unsupervised leaf-index embedding (reference ensemble.py:619-716):
    extra-random regression trees fit on uniform random targets; transform
    one-hot-encodes each sample's leaf per tree."""

    _extra = True
    _estimator_type = None

    def __init__(self, n_estimators=100, max_depth=5, n_bins=32,
                 min_samples_split=2, min_samples_leaf=1,
                 min_impurity_decrease=0.0, sparse_output=True,
                 warm_start=False, random_state=None, n_jobs=None,
                 hist_mode="auto"):
        super().__init__(
            n_estimators=n_estimators, max_depth=max_depth, n_bins=n_bins,
            max_features=1.0, min_samples_split=min_samples_split,
            min_samples_leaf=min_samples_leaf,
            min_impurity_decrease=min_impurity_decrease, bootstrap=False,
            warm_start=warm_start, random_state=random_state, n_jobs=n_jobs,
            hist_mode=hist_mode,
        )
        self.sparse_output = sparse_output

    @property
    def _classification(self):
        return False

    def fit(self, X, y=None, sample_weight=None):
        # uniform random targets (reference ensemble.py:704-706)
        rng = np.random.RandomState(self.random_state)
        y_rand = rng.uniform(size=np.asarray(X).shape[0]).astype(np.float32)
        super().fit(X, y_rand, sample_weight=sample_weight)
        # fit-time one-hot layout: one block of 2^(D+1)-1 slots per tree
        self._n_nodes = n_tree_nodes(self.max_depth)
        return self

    def fit_transform(self, X, y=None, sample_weight=None):
        return self.fit(X, y, sample_weight).transform(X)

    def transform(self, X):
        self._check_fitted()
        leaves = self.apply(X)  # (n, T)
        n, T = leaves.shape
        N = self._n_nodes
        cols = (leaves + np.arange(T)[None, :] * N).ravel()
        rows = np.repeat(np.arange(n), T)
        from scipy import sparse

        out = sparse.csr_matrix(
            (np.ones(n * T, dtype=np.float32), (rows, cols)),
            shape=(n, T * N),
        )
        return out if self.sparse_output else np.asarray(out.todense())
