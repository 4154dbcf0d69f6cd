"""
Linear estimator kernels: logistic regression, linear SVC, SGD linear
models, ridge / ridge classifier, OLS.

These supply the compute the reference borrowed from sklearn's
liblinear/lbfgs C solvers (used as the base estimator in nearly every
sk-dist example, e.g. ``/root/reference/examples/search/basic_usage.py:99``).
Each estimator is built around pure, jit/vmap-able kernels:

- ``_build_fit_kernel(static)`` → ``kernel(X, y, sample_weight, hyper)``
  returning fitted parameters. ``hyper`` values are *traced* scalars, so
  a grid of hyperparameter candidates vmaps into ONE XLA program; the
  distributed search stacks (candidate × fold) tasks on that axis and
  shards it over the TPU mesh.
- fold selection is by **sample weight masking**, never row slicing —
  static shapes are what keep XLA happy (SURVEY §7.3 item 1).

Objectives match sklearn's parameterisations where sklearn defines them:
LogisticRegression minimises ``Σ s_i·ce_i + 0.5/C·‖w‖²`` (no intercept
penalty), LinearSVC minimises ``0.5‖w‖² + C·Σ s_i·max(0, 1-y·f)²``
(squared hinge; unlike liblinear we do not penalise the intercept).
"""

import numpy as np
import jax
import jax.numpy as jnp

from ..base import BaseEstimator, ClassifierMixin, RegressorMixin
from ..sparse import (
    LinearOperator,
    PackedX,
    is_packed,
    matvec_any,
    pack_for_fit,
    sparse_to_dense_f32,
    would_pack,
)
from .solvers import (
    carry_iterate,
    lbfgs_carry_init,
    lbfgs_minimize,
    lbfgs_resume,
    sgd_carry_init,
    sgd_minimize,
    sgd_resume,
)

__all__ = [
    "LogisticRegression",
    "LinearSVC",
    "SGDClassifier",
    "Ridge",
    "RidgeClassifier",
    "LinearRegression",
]


# --------------------------------------------------------------------------
# data plumbing
# --------------------------------------------------------------------------

def as_dense_f32(X):
    """Convert input to a dense float32 ndarray (TPU-resident layout).

    Sparse input is densified through ``sparse.sparse_to_dense_f32``
    (budget guardrail, native multithreaded densifier at device-feeding
    sizes, 1-D ``csr_array`` column-vector handling). Callers on the
    FIT path should prefer :func:`prepare_fit_X`, which keeps packable
    sparse input packed (``skdist_tpu.sparse``) instead of densifying.
    """
    if hasattr(X, "toarray"):  # scipy sparse
        return sparse_to_dense_f32(X)
    elif hasattr(X, "values") and not isinstance(X, np.ndarray):  # pandas
        X = X.values
    X = np.asarray(X)
    if X.ndim == 1:
        X = X.reshape(-1, 1)
    return np.ascontiguousarray(X, dtype=np.float32)


def prepare_fit_X(X, est=None):
    """Fit-plane input routing: a :class:`~skdist_tpu.sparse.PackedX`
    when the packed-CSR sparse plane wins for this input AND the
    estimator family consumes it (``_supports_packed_X`` — the linear
    families), else a dense float32 ndarray. The predict-side entry
    points route through this too, so a sparse-fit model scores sparse
    input without ever materialising the dense matrix."""
    cls = (
        est if isinstance(est, type)
        else (type(est) if est is not None else None)
    )
    if is_packed(X):
        # already packed (a search hands its refit the X it packed)
        return X
    if cls is None or getattr(cls, "_supports_packed_X", False):
        packed = pack_for_fit(X)
        if packed is not None:
            return packed
    return as_dense_f32(X)


def fit_would_pack(X, est=None):
    """Whether :func:`prepare_fit_X` would return a ``PackedX`` for
    this (input, estimator) pair — the same routing, decided from
    shape/``indptr`` alone with no conversion or packing. Callers use
    it to order bails (e.g. the host-engine gate) BEFORE paying
    ``prepare_fit_X``'s dense f32 copy for input that will not pack."""
    cls = (
        est if isinstance(est, type)
        else (type(est) if est is not None else None)
    )
    if cls is not None and not getattr(cls, "_supports_packed_X", False):
        return False
    return would_pack(X)


def host_stage(x):
    """Stage an array for backend placement: host arrays stay host,
    device arrays stay put.

    ``_prep_fit_data`` used to ``jnp.asarray`` every leaf, which
    performed an eager uncommitted default-device transfer that the
    backend's ``batched_map`` immediately re-placed with a sharded
    ``device_put`` — and which made the reuse-broadcast cache inert
    (it keys on HOST array identity). Staying host defers the single
    transfer to the placement layer, where sharding and the opt-in
    reuse cache live.
    """
    if is_packed(x):
        return jax.tree_util.tree_map(host_stage, x)
    if hasattr(x, "sharding"):  # already a jax array: leave it be
        return x
    return np.asarray(x)


def encode_labels(y):
    """y → (int32 indices, classes array)."""
    y = np.asarray(y)
    if y.ndim == 2 and y.shape[1] == 1:
        y = y.ravel()
    classes, y_idx = np.unique(y, return_inverse=True)
    return y_idx.astype(np.int32), classes


def prepare_sample_weight(sample_weight, n):
    """Normalise user weights to a (n,) f32 vector.

    Accepts scalars (broadcast), (n,) vectors, and (n, 1) columns
    (flattened — a 2-D column would otherwise broadcast against the
    (n,) per-sample loss into an (n, n) matrix and silently corrupt
    the fit). Anything else is rejected loudly.
    """
    if sample_weight is None:
        return np.ones(n, dtype=np.float32)
    sw = np.asarray(sample_weight, dtype=np.float32)
    if sw.ndim == 0:
        return np.full(n, float(sw), dtype=np.float32)
    if sw.ndim == 2 and sw.shape[1] == 1:
        sw = sw.ravel()
    if sw.shape != (n,):
        raise ValueError(
            f"sample_weight has shape {np.shape(sample_weight)}; expected "
            f"({n},), ({n}, 1) or a scalar"
        )
    return sw


def class_weight_vector(class_weight, classes):
    """Per-class multiplier array, or None. 'balanced' resolves on device
    from effective (masked) counts inside the kernel."""
    if class_weight is None or class_weight == "balanced":
        return None
    arr = np.ones(len(classes), dtype=np.float32)
    for i, c in enumerate(classes):
        key = c.item() if hasattr(c, "item") else c
        if c in class_weight:
            arr[i] = class_weight[c]
        elif key in class_weight:
            arr[i] = class_weight[key]
        # classes absent from the dict keep weight 1 (sklearn semantics)
    return arr


def _apply_class_weight(sw, y_idx, n_classes, class_weight, cw_arr):
    """Apply class weighting on device. 'balanced' uses the weighted
    class counts of the *current* (possibly fold-masked) sample weights,
    matching sklearn's balanced heuristic n/(k·count_c)."""
    if class_weight is None:
        return sw
    onehot = jax.nn.one_hot(y_idx, n_classes, dtype=sw.dtype)
    if class_weight == "balanced":
        counts = onehot.T @ sw  # (k,)
        total = jnp.sum(sw)
        per_class = total / (n_classes * jnp.maximum(counts, 1e-12))
        per_class = jnp.where(counts > 0, per_class, 0.0)
    else:
        per_class = jnp.asarray(cw_arr)
    return sw * (onehot @ per_class)


# --------------------------------------------------------------------------
# shared linear-model machinery
# --------------------------------------------------------------------------

#: reserved keys of the _prep_fit_data data dict; everything else is
#: per-estimator fit context forwarded to kernels as ``aux``
RESERVED_DATA_KEYS = ("X", "y", "sw")


def hyper_float(value):
    """A ``_hyper_names`` value as float32. sklearn's ``tol=None``
    ("no early stopping") maps to ``-inf`` so the traced threshold
    comparison can never trigger — no other hyper accepts None."""
    return np.float32(-np.inf if value is None else value)


def extract_aux(data):
    return {k: v for k, v in data.items() if k not in RESERVED_DATA_KEYS}


def exact_matmuls(fn):
    """Trace ``fn`` under ``jax.default_matmul_precision('highest')``.

    TPU's default f32 matmul runs reduced-precision MXU passes; for the
    solver kernels that breaks the ≤1e-5 batched-vs-generic cv_results_
    parity contract (measured: 9.7e-4 default vs 1.5e-8 highest on the
    20news-shaped headline workload) — and measured *faster* end-to-end
    (21.3 vs 14.4 fits/sec), since L-BFGS converges in fewer, cleaner
    steps. Opt-in reduced precision stays available via
    ``matmul_dtype='bfloat16'``, whose dot_generals pin their own
    precision explicitly.

    Estimator classes opt out with ``_exact_matmuls = False`` (the tree
    kernels do: their one-hot/count matmul operands are exact in the
    reduced passes, so 'highest' would cost extra MXU passes for zero
    accuracy — every consumer site honours the flag so a tree compiles
    identically standalone, under a grid search, and inside a forest).
    """
    import functools

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapped


def maybe_exact_matmuls(cls, fn):
    """Apply :func:`exact_matmuls` unless ``cls`` opts out via
    ``_exact_matmuls = False`` — the single decision point for every
    kernel consumer (get_kernel, the cv kernel, the multiclass batched
    paths), so the opt-out semantics can't drift between sites."""
    return exact_matmuls(fn) if getattr(cls, "_exact_matmuls", True) else fn


def _meta_signature(meta):
    cw = meta.get("cw_arr")
    return (
        meta["n_features"],
        meta.get("n_classes"),
        tuple(cw.tolist()) if cw is not None else None,
        meta.get("y_ndim"),
        # the sparse plane is compile-shaping: a packed-X kernel and a
        # dense-X kernel of the same family must never share a cache
        # entry
        meta.get("x_format"),
    )


def _annotate_x_meta(meta, X):
    """Record the fit-data representation in ``meta`` — consumed by the
    kernel builders (packed vs dense problems) and by
    :func:`_meta_signature` (structural compile keys)."""
    if is_packed(X):
        # a bucketed X is its own treedef, so its programs never share
        # a cache entry with a padded pair's; the counts are for the
        # round stats (:func:`annotate_round_kernel_mode`)
        meta["x_format"] = "packed"
        if isinstance(X, PackedX):
            # counted where the values lie: a refit over a placed pair
            # (``_fit_placed``) brings nothing back to the host for it
            meta["x_nnz"] = int(
                (np if isinstance(X.val, np.ndarray) else jnp
                 ).count_nonzero(X.val))
            meta["x_slots"] = int(np.prod(X.idx.shape))
        else:
            # both orientations of the buckets are placed, and counted
            meta["x_nnz"], meta["x_slots"] = X.placed, X.slots
    return meta


def _annotate_stream_meta(meta, dataset):
    """The ChunkedDataset analogue of :func:`_annotate_x_meta`: a
    packed dataset's blocks run the packed kernels, and the
    representation participates in the structural compile keys exactly
    as on the resident path."""
    if getattr(dataset, "x_format", "dense") == "packed":
        meta["x_format"] = "packed"
    return meta


def kernel_mode_of(meta):
    """The kernel variant a fit with this ``meta`` runs — ``"dense"``
    or ``"packed_gather"`` for the matvec families, or the
    family tag a non-linear family stamps in ``meta["kernel_family"]``
    (the GBDT histogram trees stamp ``"hist_tree"``). The batched
    dispatch sites stamp it into
    ``backend.last_round_stats["kernel_mode"]`` so round observability
    (and the chip-leg bench captures) can attribute walls to the
    kernel that actually ran."""
    family = meta.get("kernel_family")
    if family is not None:
        return family
    if meta.get("x_format") == "packed":
        return "packed_gather"
    return "dense"


def annotate_round_kernel_mode(backend, meta):
    """Stamp :func:`kernel_mode_of` onto the backend's most recent
    round stats (no-op when the backend has none), and bill the
    registry's per-kernel-mode dispatch counter — the stamp happens
    AFTER the dispatch published its RoundStats, so the registry leg
    records it here."""
    stats = getattr(backend, "last_round_stats", None)
    if isinstance(stats, dict):
        mode = stats["kernel_mode"] = kernel_mode_of(meta)
        for key in ("x_nnz", "x_slots"):
            if key in meta:
                stats[key] = meta[key]
        from ..obs import metrics as obs_metrics

        obs_metrics.counter("rounds.kernel_mode").inc(
            1, kernel_mode=str(mode)
        )


def _ray_loss(matvec, row_loss, reg_loss, scope, linear=True):
    """``loss(w) = row_loss(matvec(w)) + reg_loss(w)`` as ``(loss,
    data_loss)`` — the one statement every L-BFGS fit problem is built
    from, whatever the representation behind ``matvec``.

    Where ``matvec`` is LINEAR in the flat weights the loss offers the
    ray below. The one that is not is the bfloat16 contract's
    (``matmul_dtype='bfloat16'`` rounds its operand, and the rounding
    of ``w + t·d`` is not the sum of two roundings): that path keeps
    the plain search, whose trial steps are its own products — along a
    ray its logits would be float32 sums and its answers a precision
    class of their own, nearer the float32 path's than the contract's
    (``LinearOperator`` has the contract).

    ``loss.ray(w, d)`` is what the solver's line search takes
    (``solvers._lbfgs_body``): ``X̃ @ (w + t·d) = X̃ @ w + t · X̃ @ d``,
    so a direction costs two forward products, every trial step a pass
    over the logits, and the accepted point's value and gradient the
    one transposed product ``X̃ᵀ r`` of ``r = ∇row_loss(z0 + t·dz)`` —
    three products an iteration however often the search halves.
    ``jax.vjp`` hands out ``z0`` and that transpose together, through
    the ``custom_vjp`` of the bucketed product as well.
    ``z0`` is taken from ``w`` anew each direction, so rounding does
    not build up along a solve."""

    def data_loss(w):
        with jax.named_scope(f"{scope}/forward_loss"):
            return row_loss(matvec(w))

    def loss(w):
        return data_loss(w) + reg_loss(w)

    def ray(w, d):
        with jax.named_scope(f"{scope}/ray"):
            z0, rmatvec = jax.vjp(matvec, w)
            dz = matvec(d)

        def along(t):
            return row_loss(z0 + t * dz) + reg_loss(w + t * d)

        def value_and_grad_at(t):
            f_rows, r = jax.value_and_grad(row_loss)(z0 + t * dz)
            f_reg, g_reg = jax.value_and_grad(reg_loss)(w + t * d)
            return f_rows + f_reg, rmatvec(r)[0] + g_reg

        return along, value_and_grad_at

    if linear:
        loss.ray = ray
    return loss, data_loss


def _start(seed, size, dtype, flat=jnp.ravel):
    """Where an L-BFGS solve starts: zeros, or a warm start's ``seed``
    — ``_warm_w0_flat``'s vector, a ``(p, k)`` weight matrix rows-major
    — taken through ``flat`` into the problem's own flat layout of
    ``size`` entries."""
    if seed is None:
        return jnp.zeros(size, dtype)
    return flat(jnp.asarray(seed, dtype)).reshape(size)


def get_kernel(cls, which, meta, static):
    """Fetch a (possibly jitted) kernel from the process-wide cache.

    Kernel builders return fresh closures; caching on the *structural*
    key (class qualname, static config, meta signature — see
    ``parallel.compile_cache.structural_key``) keeps jax.jit's own
    cache hot across estimator instances — without this every `.fit()`
    would recompile. The same memo records hit/miss counters for
    benchmark/test observability.
    """
    from ..parallel import compile_cache

    sig = compile_cache.structural_key(
        f"kernel:{which}", cls, static, _meta_signature(meta)
    )

    def build():
        fn = maybe_exact_matmuls(
            cls, getattr(cls, f"_build_{which}_kernel")(meta, static)
        )
        if which == "fit":
            fn = jax.jit(fn)
        return fn

    return compile_cache.kernel_memo(sig, build)


class _LinearModelBase(BaseEstimator):
    """Common fitted-state handling + the batched-fit contract.

    Batched-fit contract (consumed by ``distribute.search`` et al.):

    - ``_hyper_names``: constructor params that become traced scalars on
      the task axis (safe to vary within one compiled program)
    - ``_static_names``: params that change the compiled program (loop
      bounds, booleans, strings); candidates differing here are bucketed
      into separate compilations by the scheduler
    - ``_prep_fit_data(X, y, sample_weight)`` → (device pytree, meta)
    - ``_build_fit_kernel(meta, static)`` → pure fit kernel
    - ``_build_decision_kernel(meta, static)`` → params, X → raw scores
    """

    _hyper_names = ()
    _static_names = ()

    #: the linear families consume packed-CSR X (skdist_tpu.sparse)
    #: through the fit problems' matvec interface; families without the
    #: flag always receive dense input from :func:`prepare_fit_X`
    _supports_packed_X = True

    #: streamed-fit family kind consumed by ``models/streaming.py``:
    #: "lbfgs" (block-accumulated value/grad), "sgd" (block-stream
    #: epochs), "gram" (block-accumulated normal equations); None =
    #: family has no out-of-core fit
    _stream_fit_kind = None

    # ---- host-facing API -------------------------------------------------
    def fit(self, X, y=None, sample_weight=None, coef_init=None,
            intercept_init=None):
        """Fit. ``coef_init``/``intercept_init`` (sklearn shapes — a
        parent fit's ``coef_``/``intercept_``) warm-start the
        iterative families' solver carry: the L-BFGS and SGD solves
        start from the seed instead of zeros, so a refit on drifted
        data converges in a fraction of the cold iterations (the
        catalog refresh loop's public seeding surface). Closed-form
        families (the ridge/OLS direct solve) accept the seeds and
        ignore them — a direct solve has no iterate to seed, and
        accepting keeps cohort refresh generic across families."""
        from ..data import is_chunked

        if is_chunked(X):
            # out-of-core path: blocks stream through the backend's
            # double-buffered pipeline; labels/weights ride the dataset
            # (or come explicitly) as O(n) host vectors
            from .streaming import stream_fit_estimator

            return stream_fit_estimator(
                self, X, y, sample_weight,
                coef_init=coef_init, intercept_init=intercept_init,
            )
        if y is None:
            raise TypeError(
                f"{type(self).__name__}.fit requires y (only a "
                "ChunkedDataset carries its own labels)"
            )
        # packed input has no host (f64 BLAS) form: under engine='auto'
        # the packed XLA path IS the sparse engine on every platform —
        # densifying a packable hashed-text input to reach scipy would
        # reintroduce the exact host-RAM blowup this plane removes. An
        # EXPLICIT engine='host' pin is still honoured: it densifies
        # (the budget guardrail speaks when that cannot work).
        if getattr(self, "engine", None) == "host":
            X = as_dense_f32(X)
        else:
            X = prepare_fit_X(X, type(self))
        warm = coef_init is not None or intercept_init is not None
        if not is_packed(X) and self._resolve_host_engine():
            if warm:
                # the host engines already honour a flat `_warm_w0`
                # seed (the warm C-path runner's seam); scoped so a
                # later cold fit never inherits this one's seed
                self._warm_w0 = self._warm_w0_flat(
                    X.shape[1], self._warm_n_out(y),
                    coef_init, intercept_init,
                ).astype(np.float64)
                try:
                    return self._host_fit(X, y, sample_weight)
                finally:
                    del self._warm_w0
            return self._host_fit(X, y, sample_weight)
        # placed here and not by the call: a host matrix of several GiB
        # goes in row blocks (``backend.put_host_array``)
        return self._device_fit(
            X, y, sample_weight, lambda data: {**data, "X": _to_jnp(data["X"])},
            coef_init, intercept_init)

    def _device_fit(self, X, y, sample_weight, place, coef_init=None,
                    intercept_init=None):
        """The compiled fit over what ``place`` makes of the staged
        ``{"X", "y", "sw"}``: where the operands lie is where the
        kernel runs."""
        data, meta = self._prep_fit_data(X, y, sample_weight)
        static = self._static_config(meta)
        hyper = {k: jnp.asarray(hyper_float(getattr(self, k)))
                 for k in self._hyper_names}
        kernel = get_kernel(type(self), "fit", meta, _freeze(static))
        data = place({k: data[k] for k in ("X", "y", "sw")})
        args = (data["X"], data["y"], data["sw"], hyper)
        if coef_init is not None or intercept_init is not None:
            k = meta.get("n_classes", 2)
            w0 = self._warm_w0_flat(
                meta["n_features"], 1 if k <= 2 else k,
                coef_init, intercept_init,
            )
            args += ({"w0": jnp.asarray(w0)},)
        self._set_fitted(kernel(*args), meta)
        return self

    def _fit_placed(self, backend, X, y, sample_weight=None,
                    span_args=None):
        """:meth:`fit`'s device fit over an ``X`` that already lies on
        ``backend``'s mesh, dense or packed
        (``TPUBackend.place_shared``: a search hands over the operand
        its dispatches ran on); labels and weights are placed here,
        where X lies, and nothing else crosses. Row-sharded on a mesh
        with a ``data`` axis, the same jitted fit kernel runs over the
        shards, so the partitioner cuts it the way it cuts a round's
        step program — logits stay with their rows, ``X̃ᵀr`` and the
        loss's row sums are reduced between the devices — and no device
        ever holds X whole. Replicated (no ``data`` axis), the kernel
        runs over this process's first replica of each operand — a
        view of the placed buffers, no copy — so it is the one-device
        program of a standalone :meth:`fit`, to the bit, however many
        devices hold a replica. ``span_args``: where the caller traces,
        the dict that takes the ``bytes`` placed here."""
        from ..parallel.backend import row_sharded_specs, tree_nbytes

        def place(data):
            rows = {"y": data["y"], "sw": data["sw"]}
            if span_args is not None:
                span_args["bytes"] = tree_nbytes(rows)
            specs = row_sharded_specs(backend, rows, {"y": 0, "sw": 0})
            placed = {"X": data["X"], **backend.place_shared(rows, specs)}
            if specs is None:
                placed = jax.tree_util.tree_map(
                    lambda a: a.addressable_shards[0].data, placed)
            return placed

        return self._device_fit(X, y, sample_weight, place)

    def _warm_n_out(self, y):
        """Solver output columns for warm-seed shaping, before meta
        exists: classifiers fold binary to one column (the families'
        flat layout), regressors are single-output."""
        if isinstance(self, ClassifierMixin):
            k = int(np.unique(np.asarray(y)).size)
            return 1 if k <= 2 else k
        return 1

    def _warm_w0_flat(self, d, n_out, coef_init, intercept_init):
        """Map sklearn-shaped warm-start seeds (a parent fit's
        ``coef_``/``intercept_``) onto the family's flat solver
        layout: ``W`` is ``(p, n_out)`` with rows ``[:d]`` the
        coefficients and row ``d`` the intercept (when fitted),
        flattened rows-major to ``(p,)`` single-output /
        ``(p*n_out,)`` multiclass — what the host engines' ``x0`` and
        the streamed driver consume as it is. A device fit's problem
        takes it into its own flat layout (``_start``): the
        multinomial's through the ``LinearOperator`` of X's
        representation (``LinearOperator.flat``), whose inverse
        ``unpack`` reads the fitted matrix back with."""
        fit_intercept = self._fit_intercept_flag()
        d = int(d)
        n_out = int(n_out)
        p = d + (1 if fit_intercept else 0)
        W = np.zeros((p, n_out), np.float32)
        if coef_init is not None:
            coef = np.asarray(coef_init, np.float32)
            if n_out == 1:
                coef = coef.reshape(-1)
                if coef.shape[0] != d:
                    raise ValueError(
                        f"coef_init has {coef.shape[0]} features; the "
                        f"fit data has {d}"
                    )
                W[:d, 0] = coef
            elif coef.shape == (n_out, d):
                W[:d] = coef.T
            elif coef.shape == (d, n_out):
                W[:d] = coef
            else:
                raise ValueError(
                    f"coef_init shape {coef.shape} does not match "
                    f"({n_out}, {d}) (classes x features)"
                )
        if intercept_init is not None:
            b = np.asarray(intercept_init, np.float32).reshape(-1)
            if not fit_intercept:
                if np.any(b != 0):
                    raise ValueError(
                        "intercept_init is nonzero but "
                        "fit_intercept=False — this family fits no "
                        "intercept to seed"
                    )
            else:
                if b.shape[0] == 1 and n_out > 1:
                    b = np.repeat(b, n_out)
                if b.shape[0] != n_out:
                    raise ValueError(
                        f"intercept_init has {b.shape[0]} entries; "
                        f"expected {n_out}"
                    )
                W[d] = b
        return W.reshape(-1) if n_out > 1 else W[:, 0]

    def _resolve_host_engine(self):
        """True when this host-side fit should run the f64 BLAS engine
        (``models/host_linear.py``) instead of the XLA kernel.

        Estimators without a host engine always return False. With
        one: ``engine='xla'`` pins the compiled path (bit-identical to
        the mesh program — the agreement tests run under this pin),
        ``'host'`` forces the host engine, and ``'auto'`` picks host
        exactly when the default platform is a CPU — the situation the
        reference served with plain sklearn (its sc=None path) and
        where XLA-CPU prices are the wrong trade (round-4 VERDICT
        weak #6)."""
        if self._host_fit is None:
            return False
        engine = getattr(self, "engine", "xla")
        if engine not in ("auto", "host", "xla"):
            raise ValueError(
                f"engine must be 'auto', 'host' or 'xla'; got {engine!r}"
            )
        if engine == "xla":
            return False
        if engine == "host":
            return True
        if getattr(self, "matmul_dtype", None) == "bfloat16":
            return False  # explicit accelerator-precision opt-in
        import jax

        from .host_linear import host_engine_available

        return jax.default_backend() == "cpu" and host_engine_available()

    _host_fit = None  # subclasses with a host engine override

    def __getstate__(self):
        """Fitted artifacts pickle WITHOUT the warm-start scratch: the
        f64 optimum (`_w_opt64`) exists only to seed the next fit in a
        C path during a live search, and would otherwise triple a big
        model's pickle next to its f32 coefficients."""
        state = self.__dict__.copy()
        state.pop("_w_opt64", None)
        state.pop("_warm_w0", None)
        return state

    def _static_config(self, meta):
        return {k: getattr(self, k) for k in self._static_names}

    def _set_fitted(self, params, meta):
        self._params = jax.device_get(params)
        self._meta = meta
        self.n_features_in_ = meta["n_features"]
        if "classes" in meta:
            self.classes_ = meta["classes"]
        if "n_iter" in self._params:
            self.n_iter_ = np.asarray(self._params["n_iter"])

    def _check_fitted(self):
        if not hasattr(self, "_params"):
            raise AttributeError(
                f"This {type(self).__name__} instance is not fitted yet."
            )

    def decision_function(self, X):
        self._check_fitted()
        from ..data import is_chunked

        if is_chunked(X):
            raise TypeError(
                "decision_function does not take a ChunkedDataset; use "
                "skdist_tpu.distribute.batch_predict(model, dataset) "
                "(or predict/predict_proba, which route there) to "
                "stream inference block by block"
            )
        # sparse predict input stays packed when packing wins — the
        # decision kernels are representation-polymorphic (matvec_any)
        X = prepare_fit_X(X, type(self))
        static = _freeze(self._static_config(self._meta))
        kernel = get_kernel(type(self), "decision", self._meta, static)
        out = np.asarray(kernel(_to_jnp(self._params), _to_jnp(X)))
        return out

    @property
    def coef_(self):
        self._check_fitted()
        if "W" not in self._params:
            raise AttributeError(
                f"{type(self).__name__} has no linear coefficients"
            )
        W = np.asarray(self._params["W"])  # (d[+1], k) or (d[+1],)
        d = self.n_features_in_
        w = W[:d]
        if w.ndim == 1:
            return w.reshape(1, -1) if self._sklearn_2d_coef() else w
        return w.T

    @property
    def intercept_(self):
        self._check_fitted()
        if "W" not in self._params:
            raise AttributeError(
                f"{type(self).__name__} has no linear coefficients"
            )
        W = np.asarray(self._params["W"])
        d = self.n_features_in_
        if not self._fit_intercept_flag():
            k = 1 if W.ndim == 1 else W.shape[1]
            return np.zeros(k, dtype=W.dtype)
        b = W[d]
        return np.atleast_1d(b)

    def _fit_intercept_flag(self):
        return getattr(self, "fit_intercept", True)

    def _sklearn_2d_coef(self):
        return isinstance(self, ClassifierMixin)


def _freeze(d):
    """dict → hashable tuple (dict/list values frozen recursively so
    e.g. class_weight dicts can key the kernel cache)."""

    def fr(v):
        if isinstance(v, dict):
            return tuple(sorted((k, fr(x)) for k, x in v.items()))
        if isinstance(v, (list, tuple)):
            return tuple(fr(x) for x in v)
        return v

    return tuple(sorted((k, fr(v)) for k, v in d.items()))


def _to_jnp(tree):
    """The tree's leaves on the default device (a host array of
    several GiB in row blocks: ``backend.put_host_array``)."""
    from ..parallel.backend import put_host_array

    return jax.tree_util.tree_map(
        lambda a: put_host_array(a) if isinstance(a, np.ndarray)
        else jnp.asarray(a), tree)


def _split_Wb(W, d, fit_intercept, n_out):
    """W (p,) or (p,k) → (weights, bias)."""
    if W.ndim == 1:
        w, b = W[:d], (W[d] if fit_intercept else jnp.zeros((), W.dtype))
    else:
        w = W[:d]
        b = W[d] if fit_intercept else jnp.zeros((W.shape[1],), W.dtype)
    return w, b


class _LinearClassifierBase(_LinearModelBase, ClassifierMixin):
    def _prep_stream_fit(self, dataset, y, sample_weight=None):
        """Streamed-fit prep: global label encoding + meta from O(n)
        host vectors and the dataset's shape — no X materialisation.
        Returns ``(y_idx (n,), sw (n,), meta)``; the streaming driver
        slices both per block."""
        if y is None:
            raise ValueError(
                f"{type(self).__name__} needs labels: the ChunkedDataset "
                "carries none and no y was passed"
            )
        y_idx, classes = encode_labels(y)
        sw = prepare_sample_weight(sample_weight, dataset.n_rows)
        if getattr(self, "class_weight", None) == "balanced":
            raise ValueError(
                "class_weight='balanced' needs a global pass over the "
                "masked weights and is not supported on the streamed "
                "fit path yet; pass an explicit class_weight dict"
            )
        meta = _annotate_stream_meta({
            "n_features": dataset.n_features,
            "classes": classes,
            "n_classes": len(classes),
            "cw_arr": class_weight_vector(
                getattr(self, "class_weight", None), classes
            ),
        }, dataset)
        return y_idx, sw, meta

    def _prep_fit_data(self, X, y, sample_weight=None):
        y_idx, classes = encode_labels(y)
        sw = prepare_sample_weight(sample_weight, X.shape[0])
        meta = _annotate_x_meta({
            "n_features": X.shape[1],
            "classes": classes,
            "n_classes": len(classes),
            "cw_arr": class_weight_vector(getattr(self, "class_weight", None), classes),
        }, X)
        data = {
            "X": host_stage(X),
            "y": host_stage(y_idx),
            "sw": host_stage(sw),
        }
        return data, meta

    def predict(self, X):
        from ..data import is_chunked

        if is_chunked(X):
            from ..distribute.predict import batch_predict

            return batch_predict(self, X, method="predict")
        scores = self.decision_function(X)
        if scores.ndim == 1:
            idx = (scores > 0).astype(np.int64)
        else:
            idx = np.argmax(scores, axis=1)
        return self.classes_[idx]


class _LbfgsFitMixin:
    """Fit kernels for the L-BFGS linear family, derived from one
    ``_build_fit_problem(meta, static)`` definition of the objective.

    ``_build_fit_problem`` returns ``problem(X, y_idx, sw, hyper) ->
    (loss, w0, unpack)`` where ``unpack(w, n_iter)`` shapes the fitted
    params dict. The plain fit kernel and the iteration-sliced variant
    (``_build_fit_slice_kernels`` — the convergence-compacted
    scheduler's contract) are both generated from it, so the two
    execution forms minimise the *same traced objective* and the sliced
    run is bitwise identical to the unsliced solve (see
    ``models/solvers.py``)."""

    #: batched-path marker consulted by the scheduler gates
    _supports_sliced_fit = True

    #: out-of-core fit form: block-accumulated value/grad through the
    #: streamed L-BFGS driver (models/streaming.py)
    _stream_fit_kind = "lbfgs"

    @classmethod
    def _flat_w_width(cls, meta, static):
        """Flat weight-vector width of this family's solve — what the
        streamed driver allocates per task without tracing a kernel
        (streamed blocks are dense or padded pairs, whose weight
        matrices lie rows-major: ``LinearOperator.flat_size``)."""
        st = dict(static)
        p = meta["n_features"] + (1 if st["fit_intercept"] else 0)
        k = meta.get("n_classes", 2)
        return p if k <= 2 else p * k

    @classmethod
    def _batched_task_cost(cls, hyper):
        """Per-task convergence-cost heuristic for round packing
        (``hyper``: dict of per-task f32 arrays). L-BFGS family: weak
        regularisation (large C) and tight tolerance both mean more
        iterations — log-additive so neither axis drowns the other;
        ``tol <= 0`` (the tol=None → -inf mapping) never converges and
        sorts last."""
        C = np.asarray(hyper.get("C", 1.0), dtype=np.float64)
        tol = np.asarray(hyper.get("tol", 1e-4), dtype=np.float64)
        # log only on the positive mask: tol=-inf (the tol=None
        # mapping) must select -inf via where, not evaluate log(-inf)
        cost = np.log(np.maximum(C, 1e-30)) - np.where(
            tol > 0, np.log(np.where(tol > 0, tol, 1.0)), -np.inf
        )
        return np.broadcast_to(cost, np.broadcast_shapes(C.shape, tol.shape))

    @classmethod
    def _build_fit_kernel(cls, meta, static):
        problem = cls._build_fit_problem(meta, static)
        st = dict(static)
        max_iter, hist = st["max_iter"], st["history"]

        def kernel(X, y_idx, sw, hyper, aux=None):
            # warm start: the solve begins at the caller's seed (a
            # parent fit's coefficients as ``_warm_w0_flat`` hands
            # them), laid out by the problem
            loss, w0, unpack = problem(
                X, y_idx, sw, hyper,
                seed=None if aux is None else aux.get("w0"))
            w, n_iter = lbfgs_minimize(loss, w0, max_iter=max_iter,
                                       tol=hyper["tol"], history=hist)
            return unpack(w, n_iter)

        return kernel

    @classmethod
    def _build_fit_slice_kernels(cls, meta, static, n_slice):
        """Iteration-sliced fit: ``init`` starts the solve and runs the
        first ``n_slice`` iterations, ``step`` advances a carry by
        another slice, ``finalize`` shapes the fitted params from the
        (w, it) carry leaves. The carry is the solver's dict pytree —
        its ``done`` leaf is the flags-only gather the backend's
        compaction loop reads."""
        problem = cls._build_fit_problem(meta, static)
        st = dict(static)
        max_iter, hist = st["max_iter"], st["history"]
        n_slice = int(n_slice)

        def init(X, y_idx, sw, hyper, aux=None):
            loss, w0, _ = problem(X, y_idx, sw, hyper)
            carry = lbfgs_carry_init(loss, w0, max_iter=max_iter,
                                     tol=hyper["tol"], history=hist)
            return lbfgs_resume(loss, carry, n_slice, max_iter=max_iter,
                                tol=hyper["tol"], history=hist)

        def step(X, y_idx, sw, hyper, carry, aux=None):
            loss, _, _ = problem(X, y_idx, sw, hyper)
            return lbfgs_resume(loss, carry, n_slice, max_iter=max_iter,
                                tol=hyper["tol"], history=hist)

        def finalize(X, y_idx, sw, hyper, carry, aux=None):
            _, _, unpack = problem(X, y_idx, sw, hyper)
            return unpack(carry_iterate(carry), carry["it"])

        return {
            "init": init, "step": step, "finalize": finalize,
            # finalize touches only these carry leaves: retired lanes'
            # S/Y/rho history never needs to leave the device
            "finalize_keys": ("w", "it"),
            # per-lane work counts the backend books into RoundStats
            # (``iters`` / ``fevals``) as lanes retire
            "count_keys": ("it", "nfev"),
            # score-from-carry: the current iterate is a valid model at
            # every slice boundary (solvers.carry_iterate), so the ASHA
            # rung evaluator shapes params from a LIVE carry with the
            # same unpack the finalize uses — scoring never perturbs
            # the trajectory, it only reads it
            "score_params": finalize,
        }


# --------------------------------------------------------------------------
# LogisticRegression
# --------------------------------------------------------------------------

class LogisticRegression(_LbfgsFitMixin, _LinearClassifierBase):
    """L2 multinomial / binary logistic regression via jittable L-BFGS.

    sklearn-compatible surface; objective matches sklearn
    (``Σ s·ce + 0.5/C·‖w‖²``, intercept unpenalised) so coefficient and
    score parity with the reference stack holds to solver tolerance.
    ``penalty=None`` drops the ridge term entirely (sklearn's C=inf
    convention; ``C`` is then ignored). ``C`` and ``tol`` are batchable
    hyperparameters — a CV grid over C compiles to a single vmapped
    XLA program; ``penalty`` is compile-shaping (candidates bucket).

    ``engine`` picks the execution engine: ``'auto'`` (default) runs
    host-side fits on CPU platforms through the f64 BLAS solver
    (``models/host_linear.py``) and device fits through this XLA
    kernel; ``'xla'``/``'host'`` pin one engine. Both minimise the
    same objective, but stop differently at the same ``tol``: the
    host engine matches sklearn's mean-scaled ``gtol`` (iteration
    counts track sklearn), while the XLA kernel's ``max|grad| <= tol``
    is on the weight-SUM-scaled objective — tighter in absolute terms
    on large n.

    ``matmul_dtype="bfloat16"`` runs the loss/gradient matmuls (the
    FLOP bulk of L-BFGS) with bf16 inputs and f32 accumulation
    (``preferred_element_type``); the L-BFGS state, reductions, and
    regulariser stay f32. Measured on the v5e headline workload
    (round 2): ~13% faster end-to-end, cv_results_ deviation up to
    ~5e-3 from exact f32. POLICY — stays opt-in: exact f32 is the
    default because 5e-3 is 500× the framework's 1e-5 parity budget
    and can reorder close candidates. Opt in for throughput-bound
    SCREENING (wide grids / feature-elimination sweeps where you only
    need the top region of the leaderboard, not 1e-3 score
    resolution), then refit finalists at default precision. Not for
    final model selection between close candidates.
    """

    _hyper_names = ("C", "tol")
    _static_names = (
        "max_iter", "fit_intercept", "class_weight", "history",
        "matmul_dtype", "engine", "penalty",
    )

    def __init__(self, C=1.0, tol=1e-4, max_iter=100, fit_intercept=True,
                 class_weight=None, penalty="l2", random_state=None,
                 history=10, matmul_dtype=None, engine="auto"):
        self.C = C
        self.tol = tol
        self.max_iter = max_iter
        self.fit_intercept = fit_intercept
        self.class_weight = class_weight
        self.penalty = penalty
        self.random_state = random_state
        self.history = history
        self.matmul_dtype = matmul_dtype
        self.engine = engine
        if penalty not in ("l2", None, "none"):
            raise ValueError("LogisticRegression supports penalty='l2' (or None)")
        if matmul_dtype not in (None, "float32", "bfloat16"):
            raise ValueError("matmul_dtype must be None/'float32'/'bfloat16'")
        if engine not in ("auto", "host", "xla"):
            raise ValueError("engine must be 'auto', 'host' or 'xla'")

    #: the warm C-path runner (distribute/search.py) may chain fits
    _host_warm_startable = True

    def _host_fit(self, X, y, sample_weight=None):
        """Host f64 BLAS engine (scipy L-BFGS-B on the identical
        objective; ``models/host_linear.py``) — the engine 'auto'
        resolution picks for CPU-platform host fits, mirroring the
        reference's sc=None == sklearn local path.

        A caller-seeded ``_warm_w0`` (the warm C-path runner's previous
        optimum) initialises the solver when its shape matches this
        problem; the fitted instance exposes its own f64 optimum as
        ``_w_opt64`` for the next fit in the path."""
        from .host_linear import logreg_host_fit

        data, meta = self._prep_fit_data(X, y, sample_weight)
        k = meta["n_classes"]
        p = meta["n_features"] + (1 if self.fit_intercept else 0)
        n_w = p if k <= 2 else p * k
        w0 = getattr(self, "_warm_w0", None)
        if w0 is not None and np.shape(w0) != (n_w,):
            w0 = None
        # penalty=None maps to C=inf (inv_C=0), sklearn's convention;
        # re-validated because set_params bypasses __init__ — both
        # engines must reject an unsupported penalty identically
        if self.penalty not in ("l2", None, "none"):
            raise ValueError(
                "LogisticRegression supports penalty='l2' (or None)"
            )
        C_eff = (
            np.inf if self.penalty in (None, "none")
            else hyper_float(self.C)
        )
        params, w_opt = logreg_host_fit(
            np.asarray(data["X"]), np.asarray(data["y"]),
            np.asarray(data["sw"]),
            C=C_eff, tol=hyper_float(self.tol),
            max_iter=self.max_iter, fit_intercept=self.fit_intercept,
            n_classes=k, history=self.history,
            class_weight=self.class_weight, cw_arr=meta.get("cw_arr"),
            w0=w0,
        )
        self._set_fitted(params, meta)
        self._w_opt64 = w_opt
        return self

    @classmethod
    def _build_fit_problem(cls, meta, static):
        st = dict(static)
        k = meta["n_classes"]
        fit_intercept = st["fit_intercept"]
        class_weight, cw_arr = st["class_weight"], meta.get("cw_arr")
        binary = k <= 2

        md = st.get("matmul_dtype")
        if md not in (None, "float32", "bfloat16"):
            # re-validated here because set_params bypasses __init__
            raise ValueError("matmul_dtype must be None/'float32'/'bfloat16'")
        if st.get("engine", "auto") not in ("auto", "host", "xla"):
            # same guard: a typo'd engine set via set_params must not
            # silently route to the batched device path
            raise ValueError("engine must be 'auto', 'host' or 'xla'")
        penalty = st.get("penalty", "l2")
        if penalty not in ("l2", None, "none"):
            raise ValueError(
                "LogisticRegression supports penalty='l2' (or None)"
            )
        unpenalized = penalty in (None, "none")
        bf16 = md == "bfloat16"

        def problem(X, y_idx, sw, hyper, parts=False, seed=None):
            C = hyper["C"]
            # one matvec interface over dense AND packed-CSR X: the
            # operator reproduces the historical dense expressions
            # verbatim (incl. the bf16 dot_general), and routes packed
            # input through the sparse plane's gather/scatter kernels
            # — autodiff of the gather matvec IS the scatter-add
            # X.T @ r, so the whole L-BFGS solve runs O(nnz) per
            # iteration with no second code path in the solver
            op = LinearOperator(
                X, fit_intercept,
                matmul_dtype="bfloat16" if bf16 else None)
            p = op.p
            sw = _apply_class_weight(sw, y_idx, k, class_weight, cw_arr)
            d = meta["n_features"]
            # the data term and regulariser are separable closures: the
            # resident loss composes them (``_ray_loss``), and the
            # STREAMED fit evaluates data_loss per block (the term is
            # row-additive) plus reg_loss once — `parts=True` is that
            # second consumer
            if binary:
                ypm = (y_idx == (k - 1)).astype(op.dtype)  # {0,1}

                def row_loss(z):
                    return jnp.sum(sw * (jax.nn.softplus(z) - ypm * z))

                def reg_loss(w):
                    if unpenalized:  # penalty=None: sklearn's C=inf
                        return jnp.float32(0.0)
                    return 0.5 / C * jnp.dot(w[:d], w[:d])

                loss, data_loss = _ray_loss(
                    op.matvec, row_loss, reg_loss, "lr", linear=not bf16)
                w0 = _start(seed, p, op.dtype)

                def unpack(w, n_iter):
                    return {"W": w, "n_iter": n_iter}

                if parts:
                    return loss, w0, unpack, data_loss, reg_loss
                return loss, w0, unpack

            # the logits in the layout the representation's product
            # comes out in (a dense X's: classes first, rows minor —
            # ``LinearOperator.logits``); every reduction over the
            # classes runs along that axis
            ax = op.class_axis
            onehot = jax.nn.one_hot(y_idx, k, dtype=op.dtype, axis=ax)

            def row_loss(logits):
                with jax.named_scope("lr/softmax"):
                    lse = jax.nn.logsumexp(logits, axis=ax)
                    return jnp.sum(
                        sw * (lse - jnp.sum(onehot * logits, axis=ax)))

            # the weight matrix lies in the flat vector as the
            # operator lays it (``LinearOperator.weights``): no
            # statement here shapes it by hand
            def reg_loss(wflat):
                if unpenalized:  # penalty=None: sklearn's C=inf
                    return jnp.float32(0.0)
                W = op.weights(wflat, k)
                return 0.5 / C * op.coef_sq_sum(W)

            loss, data_loss = _ray_loss(
                lambda wflat: op.logits(op.weights(wflat, k)),
                row_loss, reg_loss, "lr", linear=not bf16)
            w0 = _start(seed, op.flat_size(k), op.dtype,
                        lambda W: op.flat(W.reshape(p, k)))

            def unpack(w, n_iter):
                return {"W": op.matrix(w, k), "n_iter": n_iter}

            if parts:
                return loss, w0, unpack, data_loss, reg_loss
            return loss, w0, unpack

        return problem

    @classmethod
    def _build_decision_kernel(cls, meta, static):
        st = dict(static)
        fit_intercept = st["fit_intercept"]
        d = meta["n_features"]

        @jax.jit
        def decision(params, X):
            # representation-polymorphic: X may be a dense block (the
            # predict side) OR the shared packed pair (the batched CV
            # finalize scoring a sparse fit) — matvec_any dispatches on
            # the pytree structure at trace time
            W = params["W"]
            w, b = _split_Wb(W, d, fit_intercept, meta["n_classes"])
            return matvec_any(X, w) + b

        return decision

    @classmethod
    def _build_proba_kernel(cls, meta, static):
        decision = cls._build_decision_kernel(meta, static)
        binary = meta["n_classes"] <= 2

        @jax.jit
        def proba(params, X):
            z = decision(params, X)
            if binary:
                p1 = jax.nn.sigmoid(z)
                return jnp.stack([1.0 - p1, p1], axis=1)
            return jax.nn.softmax(z, axis=1)

        return proba

    def predict_proba(self, X):
        self._check_fitted()
        from ..data import is_chunked

        if is_chunked(X):
            from ..distribute.predict import batch_predict

            return batch_predict(self, X, method="predict_proba")
        X = prepare_fit_X(X, type(self))
        static = _freeze(self._static_config(self._meta))
        kernel = get_kernel(type(self), "proba", self._meta, static)
        return np.asarray(kernel(_to_jnp(self._params), _to_jnp(X)))

    def predict_log_proba(self, X):
        return np.log(np.clip(self.predict_proba(X), 1e-15, None))


# --------------------------------------------------------------------------
# LinearSVC (squared hinge, OvR)
# --------------------------------------------------------------------------

class LinearSVC(_LbfgsFitMixin, _LinearClassifierBase):
    """L2-regularised squared-hinge linear SVM (primal, L-BFGS).

    Multiclass is one-vs-rest with all class columns solved jointly in a
    single flattened L-BFGS problem (the per-class objectives are
    separable, so the joint minimiser equals per-class minimisers while
    keeping one XLA program). Reference usage: base estimator for
    DistOneVsRestClassifier (BASELINE.json configs).
    """

    _hyper_names = ("C", "tol")
    _static_names = (
        "max_iter", "fit_intercept", "class_weight", "history", "engine",
        "loss",
    )

    def __init__(self, C=1.0, tol=1e-4, max_iter=1000, fit_intercept=True,
                 class_weight=None, loss="squared_hinge", random_state=None,
                 history=10, engine="auto"):
        self.C = C
        self.tol = tol
        self.max_iter = max_iter
        self.fit_intercept = fit_intercept
        self.class_weight = class_weight
        self.loss = loss
        self.random_state = random_state
        self.history = history
        self.engine = engine
        if loss != "squared_hinge":
            raise ValueError("LinearSVC supports loss='squared_hinge'")
        if engine not in ("auto", "host", "xla"):
            raise ValueError("engine must be 'auto', 'host' or 'xla'")

    #: the warm C-path runner (distribute/search.py) may chain fits
    _host_warm_startable = True

    def _host_fit(self, X, y, sample_weight=None):
        """Host f64 BLAS engine (scipy L-BFGS-B on the identical
        squared-hinge objective; ``models/host_linear.py``)."""
        from .host_linear import svc_host_fit

        # re-validated because set_params bypasses __init__: a
        # set_params(loss='hinge') must fail loudly on BOTH engines
        # instead of silently fitting squared hinge (ADVICE r05 #3)
        if self.loss != "squared_hinge":
            raise ValueError("LinearSVC supports loss='squared_hinge'")
        data, meta = self._prep_fit_data(X, y, sample_weight)
        k = meta["n_classes"]
        p = meta["n_features"] + (1 if self.fit_intercept else 0)
        n_w = p if k <= 2 else p * k
        w0 = getattr(self, "_warm_w0", None)
        if w0 is not None and np.shape(w0) != (n_w,):
            w0 = None
        params, w_opt = svc_host_fit(
            np.asarray(data["X"]), np.asarray(data["y"]),
            np.asarray(data["sw"]),
            C=hyper_float(self.C), tol=hyper_float(self.tol),
            max_iter=self.max_iter, fit_intercept=self.fit_intercept,
            n_classes=k, history=self.history,
            class_weight=self.class_weight, cw_arr=meta.get("cw_arr"),
            w0=w0,
        )
        self._set_fitted(params, meta)
        self._w_opt64 = w_opt
        return self

    @classmethod
    def _build_fit_problem(cls, meta, static):
        st = dict(static)
        k = meta["n_classes"]
        d = meta["n_features"]
        fit_intercept = st["fit_intercept"]
        class_weight, cw_arr = st["class_weight"], meta.get("cw_arr")
        binary = k <= 2

        if st.get("engine", "auto") not in ("auto", "host", "xla"):
            # re-validated because set_params bypasses __init__ (same
            # guard convention as LogisticRegression's matmul_dtype)
            raise ValueError("engine must be 'auto', 'host' or 'xla'")
        if st.get("loss", "squared_hinge") != "squared_hinge":
            # same convention for loss: set_params(loss='hinge') must
            # not silently fit squared hinge (ADVICE r05 #3)
            raise ValueError("LinearSVC supports loss='squared_hinge'")

        def problem(X, y_idx, sw, hyper, parts=False, seed=None):
            C = hyper["C"]
            # dense or packed-CSR X behind one matvec interface (see
            # LogisticRegression._build_fit_problem); data/reg split as
            # there — the squared-hinge sum is row-additive (streamed
            # per block), the ridge term is evaluated once
            op = LinearOperator(X, fit_intercept)
            p = op.p
            sw = _apply_class_weight(sw, y_idx, k, class_weight, cw_arr)
            if binary:
                ypm = jnp.where(y_idx == (k - 1), 1.0, -1.0).astype(op.dtype)

                def row_loss(z):
                    margin = jnp.maximum(0.0, 1.0 - ypm * z)
                    return C * jnp.sum(sw * margin**2)

                def reg_loss(w):
                    return 0.5 * jnp.dot(w[:d], w[:d])

                loss, data_loss = _ray_loss(
                    op.matvec, row_loss, reg_loss, "svc")
                w0 = _start(seed, p, op.dtype)

                def unpack(w, n_iter):
                    return {"W": w, "n_iter": n_iter}

                if parts:
                    return loss, w0, unpack, data_loss, reg_loss
                return loss, w0, unpack

            Ypm = jnp.where(jax.nn.one_hot(y_idx, k) > 0, 1.0, -1.0).astype(op.dtype)

            def row_loss(scores):
                margins = jnp.maximum(0.0, 1.0 - Ypm * scores)
                return C * jnp.sum(sw[:, None] * margins**2)

            def reg_loss(wflat):
                W = wflat.reshape(p, k)
                return 0.5 * jnp.sum(W[:d] * W[:d])

            loss, data_loss = _ray_loss(
                lambda wflat: op.matvec(wflat.reshape(p, k)),
                row_loss, reg_loss, "svc")
            w0 = _start(seed, p * k, op.dtype)

            def unpack(w, n_iter):
                return {"W": w.reshape(p, k), "n_iter": n_iter}

            if parts:
                return loss, w0, unpack, data_loss, reg_loss
            return loss, w0, unpack

        return problem

    _build_decision_kernel = LogisticRegression._build_decision_kernel


# --------------------------------------------------------------------------
# SGDClassifier
# --------------------------------------------------------------------------

class SGDClassifier(_LinearClassifierBase):
    """Mini-batch SGD linear classifier (hinge / log_loss / squared_hinge).

    TPU-first redesign of sklearn's sample-at-a-time SGD: fixed-shape
    mini-batches stepped inside ``lax.scan`` so an entire randomized
    search over ``alpha``/``eta0``/``l1_ratio`` vmaps into one program
    (BASELINE config: DistRandomizedSearchCV(SGDClassifier, covtype)).

    Early stopping honours ``tol`` with sklearn's no-validation rule:
    the mean training loss of each epoch must beat ``best - tol``
    within ``n_iter_no_change`` (=5) epochs or the task stops —
    implemented shape-statically (stopped vmap lanes freeze their
    weights while the scan runs on), so a whole randomized search still
    compiles to one program; ``n_iter_`` reports the real per-task
    epoch count. ``tol=None`` maps to ``-inf`` and reproduces the
    fixed-``max_iter`` run. One deliberate divergence: the tracked
    epoch loss is evaluated on each batch *after* its gradient step
    (sklearn accumulates the pre-update loss during the step), so
    ``n_iter_`` can differ from sklearn by an epoch or two at the same
    ``tol`` — the post-update loss is what one fused scan step can
    compute without a second forward pass per batch.

    L1 / elastic-net apply sklearn's truncated-gradient cumulative
    penalty (Tsuruoka et al.) as a stateful post-step — weights are
    clipped toward zero by their accrued-penalty deficit and genuinely
    reach exact zeros, unlike a subgradient step. The operation is
    elementwise, so a vmapped hyper search still compiles to one
    program.
    """

    _hyper_names = ("alpha", "eta0", "l1_ratio", "tol")
    _static_names = (
        "max_iter", "fit_intercept", "class_weight", "loss", "penalty",
        "learning_rate", "batch_size", "random_state",
        "n_iter_no_change", "shuffle",
    )

    def __init__(self, loss="hinge", penalty="l2", alpha=1e-4, l1_ratio=0.15,
                 max_iter=20, tol=1e-3, fit_intercept=True, eta0=0.01,
                 learning_rate="optimal", class_weight=None, random_state=0,
                 batch_size=64, n_iter_no_change=5, shuffle=True):
        self.loss = loss
        self.penalty = penalty
        self.alpha = alpha
        self.l1_ratio = l1_ratio
        self.max_iter = max_iter
        self.tol = tol
        self.fit_intercept = fit_intercept
        self.eta0 = eta0
        self.learning_rate = learning_rate
        self.class_weight = class_weight
        self.random_state = random_state
        self.batch_size = batch_size
        self.n_iter_no_change = n_iter_no_change
        # sklearn's SGD exposes shuffle too; shuffle=False is also what
        # makes a block-streamed fit bitwise-comparable to the resident
        # scan (consecutive batches don't cross row blocks)
        self.shuffle = shuffle

    _supports_sliced_fit = True

    #: out-of-core fit form: epochs as block streams (models/streaming)
    _stream_fit_kind = "sgd"

    @classmethod
    def _flat_w_width(cls, meta, static):
        st = dict(static)
        p = meta["n_features"] + (1 if st["fit_intercept"] else 0)
        k = meta.get("n_classes", 2)
        return p if k <= 2 else p * k

    @classmethod
    def _batched_task_cost(cls, hyper):
        """Round-packing cost heuristic: weak regularisation (small
        ``alpha``) and tight ``tol`` both mean more epochs before the
        no-improvement rule fires; ``tol <= 0`` (tol=None → -inf) never
        stops early and sorts last."""
        alpha = np.asarray(hyper.get("alpha", 1e-4), dtype=np.float64)
        tol = np.asarray(hyper.get("tol", 1e-3), dtype=np.float64)
        # log only on the positive mask (see _LbfgsFitMixin)
        cost = -np.log(np.maximum(alpha, 1e-30)) - np.where(
            tol > 0, np.log(np.where(tol > 0, tol, 1.0)), -np.inf
        )
        return np.broadcast_to(
            cost, np.broadcast_shapes(alpha.shape, tol.shape)
        )

    @classmethod
    def _build_fit_problem(cls, meta, static):
        """Everything the SGD solve needs, built once per (meta,
        static): ``problem(X, y_idx, sw, hyper)`` returns a dict with
        the gradient/loss/schedule closures, the initial weights and
        post-step state, and ``unpack`` — consumed identically by the
        plain fit kernel (``sgd_minimize``) and the iteration-sliced
        variant (``sgd_carry_init``/``sgd_resume``)."""
        st = dict(static)
        k = meta["n_classes"]
        d = meta["n_features"]
        fit_intercept = st["fit_intercept"]
        loss_name, penalty = st["loss"], st["penalty"]
        lr_kind = st["learning_rate"]
        max_iter, batch_size = st["max_iter"], st["batch_size"]
        n_iter_no_change = int(st["n_iter_no_change"])
        if n_iter_no_change < 1:
            # sklearn raises for this too; silently freezing after the
            # first epoch (bad_new=0 >= 0) would under-train the model
            raise ValueError(
                f"n_iter_no_change must be >= 1; got {n_iter_no_change}"
            )
        class_weight, cw_arr = st["class_weight"], meta.get("cw_arr")
        n_out = 1 if k <= 2 else k

        def pointwise_grad_factory(alpha):
            if loss_name == "log_loss":
                def dloss(z, ypm):  # dL/dz with y in {-1,1}
                    return -ypm * jax.nn.sigmoid(-ypm * z)
            elif loss_name == "hinge":
                def dloss(z, ypm):
                    return jnp.where(ypm * z < 1.0, -ypm, 0.0)
            elif loss_name == "squared_hinge":
                def dloss(z, ypm):
                    return jnp.where(ypm * z < 1.0, -2.0 * ypm * (1.0 - ypm * z), 0.0)
            else:
                raise ValueError(f"unsupported loss {loss_name!r}")
            return dloss

        seed = st["random_state"] or 0

        def problem(X, y_idx, sw, hyper):
            alpha = hyper["alpha"]
            eta0 = hyper["eta0"]
            l1_ratio = hyper["l1_ratio"]
            # dense or packed-CSR X behind one matvec interface; the
            # mini-batch forms gather the batch's packed rows, so each
            # SGD step is O(batch nnz) instead of O(batch·d)
            op = LinearOperator(X, fit_intercept)
            n = op.n
            p = op.p
            sw_full = _apply_class_weight(sw, y_idx, k, class_weight, cw_arr)
            if n_out == 1:
                Ypm = jnp.where(y_idx == (k - 1), 1.0, -1.0).astype(op.dtype)[:, None]
            else:
                Ypm = jnp.where(jax.nn.one_hot(y_idx, k) > 0, 1.0, -1.0).astype(op.dtype)
            dloss = pointwise_grad_factory(alpha)

            if loss_name == "log_loss":
                def ploss(z, ypm):
                    return jax.nn.softplus(-ypm * z)
            elif loss_name == "hinge":
                def ploss(z, ypm):
                    return jnp.maximum(0.0, 1.0 - ypm * z)
            else:  # squared_hinge
                def ploss(z, ypm):
                    return jnp.maximum(0.0, 1.0 - ypm * z) ** 2

            def loss_fn(Wf, idx):
                # weighted mean DATA loss of one batch (penalty terms
                # excluded, matching the loss sklearn's no-validation
                # early stopping tracks); joint multiclass sums the
                # separable per-column binary losses
                W = Wf.reshape(p, n_out)
                wb = sw_full[idx]
                per = ploss(op.row_matvec(idx, W), Ypm[idx]).sum(axis=1) * wb
                return jnp.sum(per) / jnp.maximum(jnp.sum(wb), 1e-12)

            def grad_fn(Wf, idx):
                W = Wf.reshape(p, n_out)
                yb = Ypm[idx]
                wb = sw_full[idx][:, None]
                z = op.row_matvec(idx, W)
                g_z = dloss(z, yb) * wb
                g = op.row_rmatvec(idx, g_z) / jnp.maximum(
                    jnp.sum(sw_full[idx]), 1e-12
                )
                if penalty in ("l2", "elasticnet"):
                    l2_mul = 1.0 if penalty == "l2" else (1.0 - l1_ratio)
                    g = g.at[:d].add(alpha * l2_mul * W[:d])
                return g.reshape(-1)

            if lr_kind == "optimal":
                # batch-adapted variant of Bottou's 'optimal' schedule:
                # sklearn's eta0 = typw suits per-SAMPLE updates; with
                # batch-MEAN gradients that initial step overshoots, so
                # the step starts at ~1 — and the 1/(alpha·t) decay
                # runs in SAMPLE time (alpha·batch_size per batch
                # step), keeping the per-sample schedule's time
                # constant. Decaying in batch-step time was ~batch×
                # too slow: the lr sat near 1 for hundreds of epochs,
                # iterates oscillated (measured: epoch losses bouncing
                # 0.8–2.7 on a problem whose optimum is 0.64), and the
                # epoch-loss series was too noisy for tol-based early
                # stopping to read.
                def lr_fn(t):
                    return 1.0 / (1.0 + alpha * batch_size * (t + 1.0))
            elif lr_kind == "invscaling":
                def lr_fn(t):
                    return eta0 / (t + 1.0) ** 0.5
            else:  # constant
                def lr_fn(t):
                    return eta0 * jnp.ones_like(t, jnp.float32)

            W0 = jnp.zeros(p * n_out, op.dtype)

            if penalty in ("l1", "elasticnet"):
                l1_mul = 1.0 if penalty == "l1" else l1_ratio

                # truncated-gradient L1 (Tsuruoka et al.'s cumulative
                # penalty — what sklearn's SGD applies): u tracks the
                # total penalty rate accrued, q what each weight has
                # actually absorbed; weights are clipped toward zero by
                # the deficit and STAY exactly zero once truncated.
                # Elementwise, so the whole search still vmaps; the l2
                # leg of elastic-net stays in grad_fn.
                def post_step(Wf, state, lr):
                    u, q = state
                    u = u + lr * alpha * l1_mul
                    W = Wf.reshape(p, n_out)
                    Q = q.reshape(p, n_out)
                    z = W[:d]  # intercept rows are not penalised
                    # exactly-zero weights stay put (sklearn's branch
                    # structure; the blind else-branch could push them
                    # negative when q > u)
                    w_trunc = jnp.where(
                        z > 0,
                        jnp.maximum(0.0, z - (u + Q[:d])),
                        jnp.where(
                            z < 0,
                            jnp.minimum(0.0, z + (u - Q[:d])),
                            z,
                        ),
                    )
                    Q = Q.at[:d].add(w_trunc - z)
                    W = W.at[:d].set(w_trunc)
                    return W.reshape(-1), (u, Q.reshape(-1))

                post_state = (jnp.float32(0.0), jnp.zeros_like(W0))
            else:
                post_step, post_state = None, ()

            def unpack(W, n_epochs):
                W = W.reshape(p, n_out)
                if n_out == 1:
                    W = W[:, 0]
                return {"W": W, "n_iter": n_epochs}

            return {
                "grad_fn": grad_fn, "loss_fn": loss_fn, "lr_fn": lr_fn,
                "post_step": post_step, "post_state": post_state,
                "W0": W0, "n": n, "key": jax.random.PRNGKey(seed),
                "unpack": unpack,
            }

        return problem

    @classmethod
    def _build_fit_kernel(cls, meta, static):
        problem = cls._build_fit_problem(meta, static)
        st = dict(static)
        max_iter, batch_size = st["max_iter"], st["batch_size"]
        n_iter_no_change = int(st["n_iter_no_change"])

        shuffle = bool(st.get("shuffle", True))

        def kernel(X, y_idx, sw, hyper, aux=None):
            pb = problem(X, y_idx, sw, hyper)
            W0 = pb["W0"]
            if aux is not None and "w0" in aux:
                # warm start: epochs begin at the caller's seed
                W0 = jnp.asarray(aux["w0"], W0.dtype).reshape(W0.shape)
            W, n_epochs = sgd_minimize(
                pb["grad_fn"], W0, pb["n"], pb["key"], max_iter,
                batch_size, pb["lr_fn"], shuffle=shuffle,
                loss_fn=pb["loss_fn"],
                tol=hyper["tol"], n_iter_no_change=n_iter_no_change,
                post_step=pb["post_step"], post_state=pb["post_state"],
            )
            return pb["unpack"](W, n_epochs)

        return kernel

    @classmethod
    def _build_fit_slice_kernels(cls, meta, static, n_slice):
        """Epoch-sliced SGD fit (the convergence-compacted scheduler's
        contract; slice unit = one epoch): same closures, carries
        advanced by ``sgd_resume`` — bitwise identical to the unsliced
        scan (stopped lanes and overhanging tails freeze in place)."""
        problem = cls._build_fit_problem(meta, static)
        st = dict(static)
        max_iter, batch_size = st["max_iter"], st["batch_size"]
        n_iter_no_change = int(st["n_iter_no_change"])
        n_slice = int(n_slice)

        shuffle = bool(st.get("shuffle", True))

        def resume(pb, carry, hyper):
            return sgd_resume(
                pb["grad_fn"], carry, n_slice, pb["n"], pb["key"],
                max_iter, batch_size, pb["lr_fn"], shuffle=shuffle,
                loss_fn=pb["loss_fn"],
                tol=hyper["tol"], n_iter_no_change=n_iter_no_change,
                post_step=pb["post_step"],
            )

        def init(X, y_idx, sw, hyper, aux=None):
            pb = problem(X, y_idx, sw, hyper)
            carry = sgd_carry_init(pb["W0"], pb["post_state"])
            return resume(pb, carry, hyper)

        def step(X, y_idx, sw, hyper, carry, aux=None):
            pb = problem(X, y_idx, sw, hyper)
            return resume(pb, carry, hyper)

        def finalize(X, y_idx, sw, hyper, carry, aux=None):
            pb = problem(X, y_idx, sw, hyper)
            return pb["unpack"](carry_iterate(carry), carry["n_done"])

        return {
            "init": init, "step": step, "finalize": finalize,
            "finalize_keys": ("w", "n_done"),
            # live-carry params for the ASHA rung evaluator (epoch
            # boundaries leave frozen/stopped lanes' weights intact, so
            # the iterate is always a scoreable model)
            "score_params": finalize,
        }

    _build_decision_kernel = LogisticRegression._build_decision_kernel

    _build_proba_kernel = LogisticRegression._build_proba_kernel

    def predict_proba(self, X):
        if self.loss != "log_loss":
            raise AttributeError(
                "predict_proba is only available with loss='log_loss'"
            )
        self._check_fitted()
        from ..data import is_chunked

        if is_chunked(X):
            from ..distribute.predict import batch_predict

            return batch_predict(self, X, method="predict_proba")
        X = prepare_fit_X(X, type(self))
        static = _freeze(self._static_config(self._meta))
        kernel = get_kernel(type(self), "proba", self._meta, static)
        return np.asarray(kernel(_to_jnp(self._params), _to_jnp(X)))


# --------------------------------------------------------------------------
# Ridge family (closed form — one cholesky solve per task, MXU-friendly)
# --------------------------------------------------------------------------

class _RidgeKernelMixin:
    @staticmethod
    def _solve(op, T, sw, alpha, d):
        """Weighted ridge: solve (XᵀSX + αI₀)W = XᵀST; intercept column
        unpenalised (I₀ has zero at the bias position). ``op`` is the
        matvec interface (``LinearOperator``): dense X keeps the MXU gram
        matmul verbatim; packed X builds the gram by the m² scatter
        (O(nnz·m) instead of O(n·d²))."""
        G, b = op.weighted_gram_rhs(sw, T)  # (p, p), (p, k)
        p = G.shape[0]
        reg = jnp.concatenate([jnp.full((d,), alpha), jnp.zeros(p - d)])
        G = G + jnp.diag(reg)
        # jitter for singular grams (e.g. alpha=0 OLS)
        G = G + 1e-8 * jnp.eye(p, dtype=G.dtype)
        W = jax.scipy.linalg.solve(G, b, assume_a="pos")
        return W


class Ridge(_LinearModelBase, RegressorMixin, _RidgeKernelMixin):
    """Closed-form weighted ridge regression. ``alpha`` is batchable, so
    a CV sweep over alphas × folds is one vmapped solve."""

    _hyper_names = ("alpha",)
    _static_names = ("fit_intercept",)

    #: out-of-core fit form: block-accumulated normal equations — the
    #: gram/rhs sums stream, one solve finishes (models/streaming.py)
    _stream_fit_kind = "gram"

    def __init__(self, alpha=1.0, fit_intercept=True):
        self.alpha = alpha
        self.fit_intercept = fit_intercept

    def _prep_stream_fit(self, dataset, y, sample_weight=None):
        if y is None:
            raise ValueError(
                f"{type(self).__name__} needs targets: the "
                "ChunkedDataset carries none and no y was passed"
            )
        y = np.asarray(y, dtype=np.float32)
        sw = prepare_sample_weight(sample_weight, dataset.n_rows)
        meta = _annotate_stream_meta(
            {"n_features": dataset.n_features, "y_ndim": y.ndim}, dataset
        )
        return y, sw, meta

    def _prep_fit_data(self, X, y, sample_weight=None):
        y = np.asarray(y, dtype=np.float32)
        sw = prepare_sample_weight(sample_weight, X.shape[0])
        meta = _annotate_x_meta(
            {"n_features": X.shape[1], "y_ndim": y.ndim}, X
        )
        data = {"X": host_stage(X), "y": host_stage(y), "sw": host_stage(sw)}
        return data, meta

    @classmethod
    def _build_fit_kernel(cls, meta, static):
        st = dict(static)
        fit_intercept = st["fit_intercept"]
        d = meta["n_features"]

        def kernel(X, y, sw, hyper, aux=None):
            alpha = hyper["alpha"]
            op = LinearOperator(X, fit_intercept)
            T = y.reshape(y.shape[0], -1)
            W = cls._solve(op, T, sw, alpha, d)
            if meta.get("y_ndim", 1) == 1:
                W = W[:, 0]
            return {"W": W}

        return kernel

    @classmethod
    def _build_decision_kernel(cls, meta, static):
        st = dict(static)
        fit_intercept = st["fit_intercept"]
        d = meta["n_features"]

        @jax.jit
        def decision(params, X):
            W = params["W"]
            w, b = _split_Wb(W, d, fit_intercept, 1)
            return matvec_any(X, w) + b

        return decision

    def predict(self, X):
        from ..data import is_chunked

        if is_chunked(X):
            from ..distribute.predict import batch_predict

            return batch_predict(self, X, method="predict")
        return self.decision_function(X)

    def _sklearn_2d_coef(self):
        return False


class LinearRegression(Ridge):
    """OLS as ridge with alpha=0 (tiny jitter for rank safety)."""

    _hyper_names = ()
    _static_names = ("fit_intercept",)

    def __init__(self, fit_intercept=True):
        self.fit_intercept = fit_intercept
        self.alpha = 0.0

    def fit(self, X, y=None, sample_weight=None, coef_init=None,
            intercept_init=None):
        self.alpha = 0.0
        return super().fit(X, y, sample_weight, coef_init=coef_init,
                           intercept_init=intercept_init)

    @classmethod
    def _build_fit_kernel(cls, meta, static):
        inner = Ridge._build_fit_kernel.__func__(cls, meta, static)

        def kernel(X, y, sw, hyper, aux=None):
            hyper = dict(hyper)
            hyper.setdefault("alpha", jnp.float32(0.0))
            return inner(X, y, sw, hyper)

        return kernel


class RidgeClassifier(_LinearClassifierBase, _RidgeKernelMixin):
    """Ridge on ±1 targets; predict via argmax/sign of the decision."""

    _hyper_names = ("alpha",)
    _static_names = ("fit_intercept", "class_weight")

    _stream_fit_kind = "gram"

    def __init__(self, alpha=1.0, fit_intercept=True, class_weight=None):
        self.alpha = alpha
        self.fit_intercept = fit_intercept
        self.class_weight = class_weight

    @classmethod
    def _build_fit_kernel(cls, meta, static):
        st = dict(static)
        fit_intercept = st["fit_intercept"]
        class_weight, cw_arr = st["class_weight"], meta.get("cw_arr")
        d = meta["n_features"]
        k = meta["n_classes"]

        def kernel(X, y_idx, sw, hyper, aux=None):
            alpha = hyper["alpha"]
            op = LinearOperator(X, fit_intercept)
            sw = _apply_class_weight(sw, y_idx, k, class_weight, cw_arr)
            if k <= 2:
                T = jnp.where(y_idx == (k - 1), 1.0, -1.0).astype(op.dtype)[:, None]
            else:
                T = jnp.where(jax.nn.one_hot(y_idx, k) > 0, 1.0, -1.0).astype(op.dtype)
            W = cls._solve(op, T, sw, alpha, d)
            if k <= 2:
                W = W[:, 0]
            return {"W": W}

        return kernel

    _build_decision_kernel = LogisticRegression._build_decision_kernel
