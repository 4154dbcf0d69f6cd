"""
Histogram-based decision trees as pure XLA kernels.

The reference delegated tree building to sklearn's Cython
``tree.fit`` (``/root/reference/skdist/distribute/ensemble.py:106-108``)
— exact, sorted, data-dependent-shape split search that XLA cannot
express. These kernels use the accelerator-native alternative
(LightGBM / XGBoost-hist style):

1. features are quantile-binned once (``ops/binning.py``);
2. the tree grows breadth-first to a *static* ``max_depth``; the node
   assignment of every sample is a vector updated level by level;
3. per-level split search is a histogram reduction — scatter-add of
   per-sample weighted channel vectors into (node, feature, bin,
   channel) — followed by cumulative sums over bins; Gini (or variance)
   gain is evaluated for every (feature, bin) in parallel;
4. row subsets (bootstrap, CV folds, OvR masks) are 0/1 sample weights;
   a dedicated count channel tracks *unweighted* occupancy so
   min_samples rules behave like sklearn's.

Everything is fixed-shape, so a whole forest vmaps over the tree axis
into one compiled program (``models/forest.py``), and the distributed
ensembles shard that axis over the TPU mesh (``distribute/ensemble.py``)
— where the reference shipped one Spark task per tree
(``ensemble.py:304-322``).

Divergences from sklearn (inherent to the histogram approach; mirrored
by every GPU/TPU tree library): split thresholds are bin boundaries,
``max_depth`` is mandatory-static (default 8), min_samples rules are
evaluated on histogram counts.
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..base import BaseEstimator, ClassifierMixin, RegressorMixin
from ..ops.binning import apply_bins, quantile_bin_edges
from .linear import (
    _freeze,
    as_dense_f32,
    encode_labels,
    get_kernel,
    host_stage,
    prepare_sample_weight,
)

__all__ = [
    "DecisionTreeClassifier",
    "DecisionTreeRegressor",
    "ExtraTreeClassifier",
    "ExtraTreeRegressor",
    "build_tree_kernel",
    "histogram_node_scores",
    "matmul_lane_cap",
    "newton_channels",
    "pick_level_splits",
    "tree_predict_kernel",
]

_NEG = -1e30


def n_tree_nodes(max_depth):
    return 2 ** (max_depth + 1) - 1


def histogram_node_scores(hist_cum, lam=None, *, newton=False,
                          classification=False, K=1):
    """hist_cum (d, nl, B, C) cumulative over bins → per-(f, node,
    threshold) gain proxies + counts. Returns (gain, cnt_l, cnt_r,
    node_totals) with node_totals (d, nl, C). ``lam`` is the
    traced Newton λ (only consumed by the newton objective).

    Module-level so out-of-core drivers (``models/streaming.py``) can
    score histograms gathered across blocks with the exact ops the
    resident kernel traces — resident-vs-streamed parity is by shared
    code, not by reimplementation."""
    tot = hist_cum[:, :, -1, :]  # (d, nl, C)
    L = hist_cum  # left stats for threshold t = bins <= t
    R = tot[:, :, None, :] - L
    cnt_l = L[..., -1]
    cnt_r = R[..., -1]
    if newton:
        g_l, h_l = L[..., 0], L[..., 1]
        g_r, h_r = R[..., 0], R[..., 1]
        g_t, h_t = tot[..., 0], tot[..., 1]
        gain = (
            g_l**2 / jnp.maximum(h_l + lam, 1e-12)
            + g_r**2 / jnp.maximum(h_r + lam, 1e-12)
            - (g_t**2 / jnp.maximum(h_t + lam, 1e-12))[:, :, None]
        )
    elif classification:
        wl = jnp.sum(L[..., :K], axis=-1)
        wr = jnp.sum(R[..., :K], axis=-1)
        sl = jnp.sum(L[..., :K] ** 2, axis=-1) / jnp.maximum(wl, 1e-12)
        sr = jnp.sum(R[..., :K] ** 2, axis=-1) / jnp.maximum(wr, 1e-12)
        st = jnp.sum(tot[..., :K] ** 2, axis=-1) / jnp.maximum(
            jnp.sum(tot[..., :K], axis=-1), 1e-12
        )
        # (Σ wt·gini improvements): decrease·W_root = sl + sr - st
        gain = sl + sr - st[:, :, None]
    else:
        w_l, wy_l, wy2_l = L[..., 0], L[..., 1], L[..., 2]
        w_r, wy_r, wy2_r = R[..., 0], R[..., 1], R[..., 2]
        sse_l = wy2_l - wy_l**2 / jnp.maximum(w_l, 1e-12)
        sse_r = wy2_r - wy_r**2 / jnp.maximum(w_r, 1e-12)
        wt, wy_t, wy2_t = tot[..., 0], tot[..., 1], tot[..., 2]
        sse_t = wy2_t - wy_t**2 / jnp.maximum(wt, 1e-12)
        gain = sse_t[:, :, None] - (sse_l + sse_r)
    return gain, cnt_l, cnt_r, tot


def pick_level_splits(gain, node_cnt, *, min_samples_split, w_root,
                      min_impurity_decrease):
    """Pick the best (feature, threshold) per node from masked gains.

    ``gain`` (d, nl, B) with invalid cells already at ``_NEG``;
    ``node_cnt`` (nl,) unweighted occupancy. Returns
    (best_f, best_t, best_gain, do_split). Shared by the resident
    level loop and the streamed host chooser."""
    nl = gain.shape[1]
    B = gain.shape[2]
    gain_fb = jnp.transpose(gain, (1, 0, 2)).reshape(nl, -1)
    best_flat = jnp.argmax(gain_fb, axis=1)
    best_gain = jnp.take_along_axis(
        gain_fb, best_flat[:, None], axis=1
    )[:, 0]
    best_f = (best_flat // B).astype(jnp.int32)
    best_t = (best_flat % B).astype(jnp.int32)
    decrease = best_gain / jnp.maximum(w_root, 1e-12)
    do_split = (
        (best_gain > 1e-12)
        & (decrease >= min_impurity_decrease)
        & (node_cnt >= min_samples_split)
    )
    return best_f, best_t, best_gain, do_split


def resolve_hist_config(n_features, n_bins, hist_mode="auto",
                        hist_block=None, allow_native=True,
                        fractional_weights=False):
    """Concrete ``(hist_mode, hist_block)`` for this platform + shape.

    ``"auto"`` takes the MEASURED per-platform winner from
    ``models/hist_calib.json`` (written by ``build_tools/
    tpu_tree_sweep.py``) with a width guard — matmul/pallas contract a
    (n, d·B)-sized one-hot, so they degrade to scatter above the
    calibrated ``d·B`` bound. Platforms with no calibration fall back
    to the shape heuristic (matmul on accelerators at tabular widths).
    Resolution happens OUTSIDE the kernel caches, so recalibrating
    mid-process (the sweep does) takes effect on the next fit.

    ``allow_native=False`` is set by callers that need an IN-PROGRAM
    (XLA) algorithm — distributed fits sharding the tree axis over the
    mesh, and ``build_tree_kernel`` itself. A calibrated/explicit
    ``"native"`` (the host C engine, ``models/native_forest.py``) then
    re-resolves to the platform shape heuristic instead — NOT blindly
    to scatter, which would be the wrong engine on a TPU whose host
    happens to win the local sweep.

    ``fractional_weights=True`` declares that the fit's effective
    per-sample weights are NOT integers (class_weight, non-integral
    sample_weight): a calibrated ``matmul_sib`` pick under ``"auto"``
    then degrades to plain ``matmul`` — sibling subtraction is exact
    only when histogram entries are exact in f32 (integer counts), and
    fractional weights can round and flip near-tie splits. An EXPLICIT
    ``hist_mode='matmul_sib'`` is honoured as-is (the user owns the
    trade).
    """
    from .hist_calib import DEFAULT_MAX_MATMUL_DB, get_calibration

    d, B = n_features, n_bins
    explicit_native = hist_mode == "native"
    resolved = hist_mode == "auto"  # every non-explicit path descends
    calib = get_calibration(jax.default_backend()) or {}
    if hist_mode == "auto":
        hist_mode = calib["mode"] if calib else "_heuristic"
    if hist_mode == "native" and not allow_native:
        if explicit_native:
            # an explicit opt-in must not silently downgrade to the
            # engine the user opted out of — only 'auto' re-resolves
            raise ValueError(
                "hist_mode='native' is the host (LocalBackend) tree "
                "engine and cannot run inside an XLA program "
                "(distributed mesh fits, batched search kernels); use "
                "'auto' or an XLA mode ('scatter'/'matmul'/'pallas')"
            )
        # prefer the sweep's MEASURED best XLA engine (and its
        # measured block size) over the shape heuristic
        xla = calib.get("xla_mode")
        if xla in ("scatter", "matmul", "matmul_sib", "pallas"):
            hist_mode = xla
            if hist_block is None:
                hist_block = (
                    calib.get("xla_hist_block") or calib.get("hist_block")
                )
        else:
            hist_mode = "_heuristic"
    if hist_mode == "_heuristic":
        hist_mode = "matmul" if jax.default_backend() != "cpu" else "scatter"
    if resolved and hist_mode == "matmul_sib" and fractional_weights:
        # calibrated auto default only for integer-effective-weight
        # fits (ADVICE r05 #4): the sweep measures speed, not the
        # f32 rounding of fractional-weight sibling subtraction
        hist_mode = "matmul"
    # single width guard for every RESOLVED path (an explicit
    # matmul/pallas request is honoured as-is): the one-hot contraction
    # is (n, d·B)-sized, degrade to scatter above the calibrated bound
    if (resolved and hist_mode in ("matmul", "matmul_sib", "pallas")
            and d * B > calib.get("max_matmul_db", DEFAULT_MAX_MATMUL_DB)):
        hist_mode = "scatter"
    # the compiled pallas histogram needs n_bins >= 8 (TPU sublane
    # tiling): a RESOLVED pick degrades to the shape heuristic — only
    # an explicit hist_mode='pallas' request raises (build_tree_kernel)
    if resolved and hist_mode == "pallas" and B < 8:
        hist_mode = (
            "matmul" if jax.default_backend() != "cpu" else "scatter"
        )
    if hist_block is None:
        hist_block = calib.get("hist_block") or 8
    return hist_mode, int(hist_block)


#: largest right factor ``(lanes, n, nl·C)`` the one-hot matmul engines
#: may materialise in one program, in elements. Measured on the v5e
#: (jax/jaxlib 0.9.0, libtpu 0.0.34): a round of 53 forest lanes at
#: 200,000 rows and depth 8 — 4.07e9 elements at the last level, 4.3e9
#: once the lane axis is padded to the tile, past 2^32 — compiled, ran
#: without an error and returned histograms that left level 7 almost
#: unsplit; rounds of 32 to 48 lanes (2.5e9 to 3.7e9) grew exactly the
#: trees of small rounds. The bound is the conservative side of that
#: reading: what a signed 32-bit offset can address.
MATMUL_MAX_OPERAND_ELEMS = 2 ** 31


def matmul_lane_cap(n_samples, max_depth, channels):
    """How many lanes (trees of a round) the ``matmul``/``matmul_sib``
    engines may grow in one program at this shape: the last level's
    right factor is ``(lanes, n, 2^(D-1)·C)`` and must stay under
    :data:`MATMUL_MAX_OPERAND_ELEMS`. At least 1 — a single lane past
    the bound has no smaller round to fall back to."""
    per_lane = int(n_samples) * 2 ** max(int(max_depth) - 1, 0) * int(channels)
    return max(1, MATMUL_MAX_OPERAND_ELEMS // max(per_lane, 1))


def build_tree_kernel(n_features, n_bins, channels, max_depth, max_features,
                      min_samples_split, min_samples_leaf,
                      min_impurity_decrease, extra, classification,
                      hist_block=None, hist_mode="auto",
                      fractional_weights=False, newton=False):
    """Returns ``kernel(Xb, Ych, key) -> tree`` growing one tree.

    - ``Xb`` (n, d) int32 binned features
    - ``Ych`` (n, C) f32 per-sample channels:
      classification C = K + 1: [w·onehot(y) ..., count(w>0)]
      regression C = 4: [w, w·y, w·y², count(w>0)]
      newton C = 3: [s·g, s·h, count(s>0)] (gradient/hessian channels)
    - ``key``: PRNG key (feature subsampling / random thresholds)

    ``newton=True`` is the gradient-boosting objective (XGBoost /
    LightGBM / sklearn-HistGradientBoosting lineage): the channels are
    per-sample gradient/hessian sums of the boosting loss, split gain
    is ``G_L²/(H_L+λ) + G_R²/(H_R+λ) − G_T²/(H_T+λ)`` and the leaf
    value is the Newton step ``−G/(H+λ)``. λ (``l2_regularization``)
    arrives as the kernel's optional 4th argument — a *traced* scalar,
    so a CV grid over λ vmaps into one compiled program. The histogram
    machinery (scatter / matmul / matmul_sib / pallas engines) is
    channel-agnostic and runs unchanged; only the gain and the leaf
    read differently. ``classification`` must be False (the tree
    regresses the Newton step whatever the boosting loss is).

    ``tree`` = {feat (N,), thr (N,), is_split (N,), leaf (N, K_out)}
    with N = 2^(D+1)-1 heap-indexed nodes (children of i: 2i+1, 2i+2).

    ``hist_mode`` selects the per-level histogram algorithm:

    - ``"scatter"``: blocked scatter-add (one segment-add per feature
      block). Best on CPU, where scatters are cheap and FLOPs are not.
    - ``"matmul"``: one-hot matmul — ``hist = Xoh.T @ (nodeoh ⊗ Ych)``
      where ``Xoh`` (n, d·B) is the LEVEL-INVARIANT one-hot of the
      binned features (hoisted out of the level loop) and the right
      factor (n, nl·C) re-weights each sample's channels by its node.
      This trades redundant FLOPs for MXU throughput: the whole
      histogram becomes one large dense matmul per level, the shape TPU
      hardware is built for, displacing the scatter that round-1
      measured as the forest bottleneck (42s vs sklearn's 7.4s per 100
      trees on 20k×54). f32 accumulation, exact 0/1 one-hots.
    - ``"pallas"``: the same contraction as ``"matmul"`` executed by a
      Pallas TPU kernel (``ops/pallas_hist.py``) that builds both
      one-hot factors on the fly in VMEM — nothing of size (n, d·B) or
      (n, nl·C) is ever materialised in HBM. Off-TPU it runs through
      the Pallas interpreter (correct but slow; tests only). The
      compiled path assumes ``n_bins >= 8`` (TPU sublane tiling).
    - ``"matmul_sib"``: the matmul engine with sibling subtraction
      (LightGBM's classic halving): below the root, only LEFT-child
      histograms are computed by matmul — each right child is its
      parent's (previous level's) histogram minus the left sibling,
      zeroed for children of non-split parents. Halves the dominant
      per-level contraction FLOPs. Exactness: with integer effective
      weights (the default — bootstrap counts × unit sample_weight)
      every histogram entry below 2^24 is exact in f32, so the
      subtraction is bitwise-identical to direct summation (measured:
      identical trees on tie-heavy fuzz data); fractional
      class/sample weights can round and flip near-tie splits (the
      same flip class as the xla-vs-native near-ties, NOTES round-4
      fuzz). The sweep may therefore calibrate it as the ``"auto"``
      default, but ``resolve_hist_config`` honours that calibration
      ONLY for integer-effective-weight fits — callers declaring
      ``fractional_weights=True`` (class_weight / non-integral
      sample_weight) degrade the calibrated pick to plain
      ``"matmul"``; an explicit ``hist_mode='matmul_sib'`` is always
      honoured.
    - ``"auto"``: the MEASURED per-platform winner from
      ``models/hist_calib.json`` (written by the on-chip sweep,
      ``build_tools/tpu_tree_sweep.py``), with a width guard — matmul /
      pallas degrade to scatter above the calibrated ``d·B`` bound.
      Platforms with no calibration entry fall back to the shape
      heuristic: matmul on accelerators for tabular widths, scatter
      otherwise. ``hist_block=None`` likewise takes the calibrated
      scatter block size.

    A fifth engine, ``"native"`` (the host C kernels of
    ``models/native_forest.py``), lives OUTSIDE this builder: estimator
    ``fit`` paths route to it before building an XLA kernel, and a
    calibrated ``"native"`` re-resolves here to the sweep's measured
    XLA runner-up (``resolve_hist_config(allow_native=False)``).
    """
    d, B, C, D = n_features, n_bins, channels, max_depth
    if newton and classification:
        raise ValueError(
            "newton=True grows a regression tree on gradient/hessian "
            "channels; pass classification=False (the boosting LOSS, "
            "not the tree, decides classification semantics)"
        )
    K = C - 1 if classification else 1  # leaf output width
    # allow_native=False: the host C engine (models/native_forest.py) is
    # selected at the FOREST level (forest.py routes around the XLA
    # kernel); this builder needs an in-program algorithm
    hist_mode, hist_block = resolve_hist_config(
        d, B, hist_mode, hist_block, allow_native=False,
        fractional_weights=fractional_weights,
    )
    if hist_mode not in ("scatter", "matmul", "matmul_sib", "pallas"):
        raise ValueError(
            f"hist_mode must be 'auto', 'scatter', 'matmul', "
            f"'matmul_sib' or 'pallas'; got {hist_mode!r}"
        )
    if hist_mode == "pallas" and B < 8:
        raise ValueError(
            f"hist_mode='pallas' requires n_bins >= 8 (TPU sublane "
            f"tiling); got n_bins={B}"
        )

    def node_scores(hist_cum, lam=None):
        return histogram_node_scores(
            hist_cum, lam, newton=newton,
            classification=classification, K=K,
        )

    def kernel(Xb, Ych, key, l2=None):
        n = Xb.shape[0]
        N = n_tree_nodes(D)
        lam = (
            (jnp.float32(0.0) if l2 is None else l2) if newton else None
        )
        feat = jnp.full((N,), -1, jnp.int32)
        thr = jnp.zeros((N,), jnp.int32)
        is_split = jnp.zeros((N,), bool)
        gain_rec = jnp.zeros((N,), jnp.float32)
        node_id = jnp.zeros((n,), jnp.int32)
        if newton:
            w_root = jnp.sum(Ych[:, 1])  # total hessian mass
        elif classification:
            w_root = jnp.sum(Ych[:, :K])
        else:
            w_root = jnp.sum(Ych[:, 0])

        # level-invariant histogram inputs, hoisted out of the unrolled
        # level loop
        if hist_mode in ("matmul", "matmul_sib"):
            # (n, d·B) one-hot of the binned features — the left matmul
            # factor for every level
            Xoh = jax.nn.one_hot(Xb, B, dtype=Ych.dtype).reshape(n, d * B)
        elif hist_mode == "pallas":
            pass  # one-hot factors are built inside the kernel, in VMEM
        else:
            # padded feature-major bins and the tiled channel matrix
            # each scatter consumes
            fb = min(hist_block, d)
            n_blocks = -(-d // fb)
            d_pad = n_blocks * fb
            XbT = Xb.T
            if d_pad != d:
                XbT = jnp.concatenate(
                    [XbT, jnp.zeros((d_pad - d, XbT.shape[1]), XbT.dtype)]
                )
            XbT_blocks = XbT.reshape(n_blocks, fb, -1)
            Ych_tiled = jnp.tile(Ych, (fb, 1))  # (fb*n, C)

        prev_hist = prev_split = None  # matmul_sib level-to-level carry
        for level in range(D):
            start = 2**level - 1
            nl = 2**level
            rel = node_id - start
            at_level = (node_id >= start) & (node_id < start + nl)

            if hist_mode == "matmul_sib" and level > 0:
                # ---- sibling subtraction: matmul ONLY the left
                # children (parent-slot one-hot masked to left-going
                # samples, half the contraction width), then derive
                # each right child as parent minus left sibling —
                # children of unsplit parents are zeroed (their "right
                # = parent - 0" would otherwise resurrect the parent's
                # samples)
                nh = nl // 2
                left = at_level & (rel % 2 == 0)
                parent_oh = jax.nn.one_hot(
                    jnp.clip(rel // 2, 0, nh - 1), nh, dtype=Ych.dtype
                ) * left[:, None].astype(Ych.dtype)
                NW = (parent_oh[:, :, None] * Ych[:, None, :]).reshape(
                    n, nh * C
                )
                hist_left = lax.dot_general(
                    Xoh, NW, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ).reshape(d, B, nh, C).transpose(0, 2, 1, 3)
                split_mask = prev_split.astype(jnp.float32)[
                    None, :, None, None
                ]
                hist_right = (prev_hist - hist_left) * split_mask
                hist = jnp.stack(
                    [hist_left, hist_right], axis=2
                ).reshape(d, nl, B, C)
            elif hist_mode in ("matmul", "matmul_sib"):
                # ---- histogram as one MXU matmul per level:
                # (d·B, n) @ (n, nl·C) with samples not at this level
                # zeroed by the node one-hot
                level_oh = jax.nn.one_hot(
                    jnp.clip(rel, 0, nl - 1), nl, dtype=Ych.dtype
                ) * at_level[:, None].astype(Ych.dtype)
                NW = (level_oh[:, :, None] * Ych[:, None, :]).reshape(
                    n, nl * C
                )
                hist = lax.dot_general(
                    Xoh, NW, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                hist = hist.reshape(d, B, nl, C).transpose(0, 2, 1, 3)
            elif hist_mode == "pallas":
                # ---- same contraction, Pallas kernel: one-hot factors
                # built in VMEM, nothing (n, d·B)-sized in HBM
                from ..ops.pallas_hist import level_histogram

                node_key = jnp.where(at_level, rel, nl).astype(jnp.int32)
                hist = level_histogram(
                    Xb, node_key, Ych, nl=nl, n_bins=B
                )
            else:
                # ---- histogram: scan over feature BLOCKS, one scatter
                # per block (fewer, larger scatters pipeline better
                # than d tiny ones; block size bounds the buffer)
                seg_node = jnp.where(at_level, rel * B, nl * B * fb)
                f_off = (jnp.arange(fb) * (nl * B))[:, None]

                def hist_blk(_, xcols, seg_node=seg_node, f_off=f_off,
                             nl=nl):
                    # xcols (fb, n)
                    seg = jnp.minimum(seg_node[None, :] + f_off + xcols,
                                      nl * B * fb)
                    h = jnp.zeros((nl * B * fb + 1, C), Ych.dtype)
                    h = h.at[seg.reshape(-1)].add(Ych_tiled)
                    return None, h[: nl * B * fb].reshape(fb, nl, B, C)

                _, hist = lax.scan(hist_blk, None, XbT_blocks)
                hist = hist.reshape(d_pad, nl, B, C)[:d]  # (d, nl, B, C)
            cum = jnp.cumsum(hist, axis=2)
            gain, cnt_l, cnt_r, tot = node_scores(cum, lam)

            # ---- validity
            node_cnt = tot[0, :, -1]  # (nl,) unweighted occupancy
            ok = (cnt_l >= min_samples_leaf) & (cnt_r >= min_samples_leaf)
            gain = jnp.where(ok, gain, _NEG)

            lkey = jax.random.fold_in(key, level)
            if max_features < d:
                r = jax.random.uniform(lkey, (nl, d))
                kth = jnp.sort(r, axis=1)[:, max_features - 1]
                fmask = (r <= kth[:, None]).T  # (d, nl)
                gain = jnp.where(fmask[:, :, None], gain, _NEG)
            if extra:
                # random threshold per (feature, node) within the
                # occupied bin range — ExtraTrees semantics on bins
                cnt_bins = hist[..., -1]  # (d, nl, B)
                occ = cnt_bins > 0
                lo = jnp.argmax(occ, axis=2)  # first occupied
                hi = B - 1 - jnp.argmax(occ[:, :, ::-1], axis=2)  # last
                u = jax.random.uniform(jax.random.fold_in(lkey, 1), (d, nl))
                t_rand = lo + jnp.floor(u * jnp.maximum(hi - lo, 1)).astype(
                    jnp.int32
                )
                t_rand = jnp.clip(t_rand, 0, B - 2)
                sel = (
                    jnp.arange(B)[None, None, :] == t_rand[:, :, None]
                )
                gain = jnp.where(sel, gain, _NEG)

            # ---- pick best (feature, threshold) per node
            best_f, best_t, best_gain, do_split = pick_level_splits(
                gain, node_cnt,
                min_samples_split=min_samples_split,
                w_root=w_root,
                min_impurity_decrease=min_impurity_decrease,
            )

            idx = start + jnp.arange(nl)
            feat = feat.at[idx].set(jnp.where(do_split, best_f, -1))
            thr = thr.at[idx].set(best_t)
            is_split = is_split.at[idx].set(do_split)
            gain_rec = gain_rec.at[idx].set(jnp.where(do_split, best_gain, 0.0))
            if hist_mode == "matmul_sib":
                prev_hist, prev_split = hist, do_split

            # ---- route samples
            f_s = best_f[jnp.clip(rel, 0, nl - 1)]
            t_s = best_t[jnp.clip(rel, 0, nl - 1)]
            split_s = do_split[jnp.clip(rel, 0, nl - 1)] & at_level
            bin_s = jnp.take_along_axis(Xb, f_s[:, None], axis=1)[:, 0]
            child = 2 * node_id + 1 + (bin_s > t_s)
            node_id = jnp.where(split_s, child, node_id)

        # ---- leaf statistics over final assignments
        stats = jnp.zeros((N, C), Ych.dtype).at[node_id].add(Ych)
        if newton:
            # Newton step per node: −G/(H+λ); empty nodes hold exact 0
            # (their stats are all-zero), so unused heap slots — and
            # unused boosting rounds' whole trees — contribute nothing
            leaf = (
                -stats[:, 0] / jnp.maximum(stats[:, 1] + lam, 1e-12)
            )[:, None]
        elif classification:
            wsum = jnp.sum(stats[:, :K], axis=1, keepdims=True)
            leaf = stats[:, :K] / jnp.maximum(wsum, 1e-12)
            leaf = jnp.where(wsum > 0, leaf, 1.0 / K)
        else:
            leaf = (stats[:, 1] / jnp.maximum(stats[:, 0], 1e-12))[:, None]
        return {
            "feat": feat, "thr": thr, "is_split": is_split, "leaf": leaf,
            "gain": gain_rec,
        }

    return kernel


def tree_predict_kernel(max_depth, return_nodes=False):
    """Returns ``predict(tree, Xb) -> leaf values (n, K_out)`` (or final
    node ids when ``return_nodes`` — the ``apply()`` analogue used by
    RandomTreesEmbedding)."""

    def predict(tree, Xb):
        n = Xb.shape[0]
        node = jnp.zeros((n,), jnp.int32)
        for _ in range(max_depth):
            f = tree["feat"][node]
            t = tree["thr"][node]
            s = tree["is_split"][node]
            b = jnp.take_along_axis(
                Xb, jnp.clip(f, 0, Xb.shape[1] - 1)[:, None], axis=1
            )[:, 0]
            child = 2 * node + 1 + (b > t)
            node = jnp.where(s, child, node)
        if return_nodes:
            return node
        return tree["leaf"][node]

    return predict


def feature_importances_from_tree(feat, gain, n_features):
    """Impurity-decrease importances (sklearn semantics), host-side."""
    imp = np.zeros(n_features, dtype=np.float64)
    mask = np.asarray(feat) >= 0
    np.add.at(imp, np.asarray(feat)[mask], np.asarray(gain)[mask])
    total = imp.sum()
    return imp / total if total > 0 else imp


# ---------------------------------------------------------------------------
# channel construction
# ---------------------------------------------------------------------------

def classification_channels(y_idx, sw, n_classes):
    oh = jax.nn.one_hot(y_idx, n_classes, dtype=jnp.float32)
    cnt = (sw > 0).astype(jnp.float32)
    return jnp.concatenate([oh * sw[:, None], cnt[:, None]], axis=1)


def regression_channels(y, sw):
    cnt = (sw > 0).astype(jnp.float32)
    return jnp.stack([sw, sw * y, sw * y * y, cnt], axis=1)


def newton_channels(g, h, sw):
    """GBDT's generalization of the channel builders above: per-sample
    gradient/hessian of the boosting loss, weighted by the (possibly
    fold-masked) sample weights, plus the unweighted-occupancy channel
    the min_samples rules read. Consumed with
    ``build_tree_kernel(newton=True, channels=3)``."""
    cnt = (sw > 0).astype(jnp.float32)
    return jnp.stack([sw * g, sw * h, cnt], axis=1)


def resolve_max_features(max_features, d):
    if max_features in (None, "none", "all"):
        return d
    if max_features == "sqrt":
        return max(1, int(np.sqrt(d)))
    if max_features == "log2":
        return max(1, int(np.log2(d)))
    if isinstance(max_features, float):
        return max(1, int(max_features * d))
    return min(d, int(max_features))


# ---------------------------------------------------------------------------
# estimator classes
# ---------------------------------------------------------------------------

class _BaseTree(BaseEstimator):
    """Single-tree estimator over the histogram kernel.

    ``splitter='random'`` gives ExtraTree behaviour (random thresholds,
    no bootstrap context). The batched-fit contract marks everything
    static: tree structure params shape the compiled program.
    """

    _hyper_names = ()
    _static_names = (
        "max_depth", "n_bins", "max_features", "min_samples_split",
        "min_samples_leaf", "min_impurity_decrease", "splitter",
        "random_state", "hist_mode",
    )
    # histogram matmul operands (one-hots, counts) are exact in TPU's
    # reduced-precision passes; forcing 'highest' would only add passes
    _exact_matmuls = False

    def __init__(self, max_depth=8, n_bins=32, max_features=None,
                 min_samples_split=2, min_samples_leaf=1,
                 min_impurity_decrease=0.0, splitter="best", random_state=0,
                 hist_mode="auto"):
        self.max_depth = max_depth
        self.n_bins = n_bins
        self.max_features = max_features
        self.min_samples_split = min_samples_split
        self.min_samples_leaf = min_samples_leaf
        self.min_impurity_decrease = min_impurity_decrease
        self.splitter = splitter
        self.random_state = random_state
        self.hist_mode = hist_mode

    @property
    def _classification(self):
        return isinstance(self, ClassifierMixin)

    def _prep_fit_data(self, X, y, sample_weight=None):
        X = as_dense_f32(X)
        sw = prepare_sample_weight(sample_weight, X.shape[0])
        edges = quantile_bin_edges(X, self.n_bins)
        # CV fold masks are 0/1, so integral sw stays integral under
        # the batched search's mask composition — prep time is the one
        # place the weights' integral-ness is decidable for the gate
        # resolve_hist_config applies to a calibrated matmul_sib
        meta = {
            "n_features": X.shape[1], "edges": edges,
            "fractional_weights": bool(np.any(sw != np.rint(sw))),
        }
        if self._classification:
            y_idx, classes = encode_labels(y)
            meta.update(classes=classes, n_classes=len(classes))
            data = {"X": host_stage(X), "y": host_stage(y_idx),
                    "sw": host_stage(sw)}
        else:
            data = {"X": host_stage(X),
                    "y": np.asarray(y, np.float32),
                    "sw": host_stage(sw)}
        # extra data-dependent fit context; the distributed search
        # forwards non-(X,y,sw) entries to the kernel as ``aux``
        data["edges"] = host_stage(edges)
        return data, meta

    def _static_config(self, meta):
        cfg = {k: getattr(self, k) for k in self._static_names}
        cfg["_n_classes"] = meta.get("n_classes", 0)
        cfg["_n_features"] = meta["n_features"]
        # rides the static config so the kernel caches key on it and
        # _build_fit_kernel can apply the matmul_sib weight gate
        cfg["_fractional_weights"] = meta.get("fractional_weights", False)
        return cfg

    @classmethod
    def _build_fit_kernel(cls, meta, static):
        st = dict(static)
        d = st["_n_features"]
        K = st["_n_classes"]
        classification = K > 0
        C = (K + 1) if classification else 4
        grow = build_tree_kernel(
            n_features=d, n_bins=st["n_bins"], channels=C,
            max_depth=st["max_depth"],
            max_features=resolve_max_features(st["max_features"], d),
            min_samples_split=st["min_samples_split"],
            min_samples_leaf=st["min_samples_leaf"],
            min_impurity_decrease=st["min_impurity_decrease"],
            extra=(st["splitter"] == "random"),
            classification=classification,
            hist_mode=st.get("hist_mode", "auto"),
            fractional_weights=st.get("_fractional_weights", False),
        )
        seed = st["random_state"] or 0

        def kernel(X, y, sw, hyper, aux=None):
            # aux carries data-dependent context (bin edges, PRNG key) so
            # the kernel itself is cacheable purely by shape/config
            edges = aux["edges"]
            Xb = apply_bins(X, edges)
            if classification:
                Ych = classification_channels(y, sw, K)
            else:
                Ych = regression_channels(y, sw)
            key = aux.get("key")
            if key is None:
                key = jax.random.PRNGKey(seed)
            tree = grow(Xb, Ych, key)
            tree["edges"] = edges  # predict-side context travels in params
            return tree

        return kernel

    @classmethod
    def _build_decision_kernel(cls, meta, static):
        st = dict(static)
        predict = tree_predict_kernel(st["max_depth"])

        @jax.jit
        def decision(params, X):
            Xb = apply_bins(X, params["edges"])
            out = predict(params, Xb)
            return out[:, 0] if out.shape[1] == 1 else out

        return decision

    def fit(self, X, y, sample_weight=None):
        data, meta = self._prep_fit_data(X, y, sample_weight)
        mode, _ = resolve_hist_config(
            meta["n_features"], self.n_bins, self.hist_mode
        )
        if mode == "native":
            from .native_forest import (
                grow_single_tree_native,
                native_supported_or_raise,
            )

            if native_supported_or_raise(
                self.n_bins, self.hist_mode == "native"
            ):
                # host C engine as a one-tree forest: a single-tree fit
                # pays NO XLA compile (cold == warm — the compile was
                # seconds for one tree). Same engine-caveat as forests:
                # subsample/threshold PRNG streams differ from the
                # device kernel's.
                Xb = np.asarray(
                    apply_bins(jnp.asarray(data["X"]),
                               jnp.asarray(meta["edges"]))
                )
                d = meta["n_features"]
                params = grow_single_tree_native(
                    Xb, data["y"], data["sw"], self.random_state or 0,
                    n_bins=self.n_bins, max_depth=self.max_depth,
                    max_features=resolve_max_features(
                        self.max_features, d
                    ),
                    min_samples_split=self.min_samples_split,
                    min_samples_leaf=self.min_samples_leaf,
                    min_impurity_decrease=self.min_impurity_decrease,
                    extra=(self.splitter == "random"),
                    classification=self._classification,
                    n_classes=meta.get("n_classes", 0) or 1,
                )
                params["edges"] = np.asarray(meta["edges"])
                self._params = params
                self._meta = meta
                self.n_features_in_ = d
                if "classes" in meta:
                    self.classes_ = meta["classes"]
                return self
        static = _freeze(self._static_config(meta))
        kernel = get_kernel(type(self), "fit", meta, static)
        aux = {"edges": jnp.asarray(meta["edges"])}
        params = kernel(data["X"], data["y"], data["sw"], {}, aux)
        self._params = jax.device_get(params)
        self._meta = meta
        self.n_features_in_ = meta["n_features"]
        if "classes" in meta:
            self.classes_ = meta["classes"]
        return self

    def _check_fitted(self):
        if not hasattr(self, "_params"):
            raise AttributeError(
                f"This {type(self).__name__} instance is not fitted yet."
            )

    def _native_walk(self, X, mode):
        """Host C walker on the single tree (viewed as a T=1 forest);
        None falls through to the XLA decision kernel."""
        if jax.default_backend() != "cpu":
            return None
        from ..native import forest_walk_native, hist_tree_available
        from ..ops.binning import apply_bins_np

        # same ordering rationale as the forest's _native_walk:
        # availability before binning; width mismatch falls through to
        # the XLA path's loud shape error
        edges = self._params["edges"]
        if not hist_tree_available() or X.shape[1] != len(edges):
            return None
        trees = {
            k: np.asarray(self._params[k])[None]
            for k in ("feat", "thr", "is_split", "leaf")
        }
        return forest_walk_native(
            apply_bins_np(X, edges), trees, self.max_depth, mode=mode,
        )

    def _leaf_values(self, X):
        self._check_fitted()
        X = as_dense_f32(X)
        out = self._native_walk(X, "predict")
        if out is not None:
            # match the decision kernel's squeeze for regressors
            return out[:, 0] if out.shape[1] == 1 else out
        static = _freeze(self._static_config(self._meta))
        kernel = get_kernel(type(self), "decision", self._meta, static)
        params = jax.tree_util.tree_map(jnp.asarray, self._params)
        return np.asarray(kernel(params, jnp.asarray(X)))

    @property
    def feature_importances_(self):
        self._check_fitted()
        return feature_importances_from_tree(
            self._params["feat"], self._params["gain"], self.n_features_in_
        )

    def apply(self, X):
        """Leaf (node) index per sample — sklearn ``tree.apply`` analogue."""
        self._check_fitted()
        X = as_dense_f32(X)
        out = self._native_walk(X, "apply")
        if out is not None:
            return out[:, 0]
        walk = tree_predict_kernel(self.max_depth, return_nodes=True)
        params = jax.tree_util.tree_map(jnp.asarray, self._params)
        Xb = apply_bins(jnp.asarray(X), params["edges"])
        return np.asarray(walk(params, Xb))


class DecisionTreeClassifier(_BaseTree, ClassifierMixin):
    def predict_proba(self, X):
        return self._leaf_values(X)

    def predict(self, X):
        return self.classes_[np.argmax(self.predict_proba(X), axis=1)]


class DecisionTreeRegressor(_BaseTree, RegressorMixin):
    def predict(self, X):
        return self._leaf_values(X)


class ExtraTreeClassifier(DecisionTreeClassifier):
    def __init__(self, max_depth=8, n_bins=32, max_features=None,
                 min_samples_split=2, min_samples_leaf=1,
                 min_impurity_decrease=0.0, splitter="random", random_state=0):
        super().__init__(
            max_depth=max_depth, n_bins=n_bins, max_features=max_features,
            min_samples_split=min_samples_split,
            min_samples_leaf=min_samples_leaf,
            min_impurity_decrease=min_impurity_decrease, splitter=splitter,
            random_state=random_state,
        )


class ExtraTreeRegressor(DecisionTreeRegressor):
    def __init__(self, max_depth=8, n_bins=32, max_features=None,
                 min_samples_split=2, min_samples_leaf=1,
                 min_impurity_decrease=0.0, splitter="random", random_state=0):
        super().__init__(
            max_depth=max_depth, n_bins=n_bins, max_features=max_features,
            min_samples_split=min_samples_split,
            min_samples_leaf=min_samples_leaf,
            min_impurity_decrease=min_impurity_decrease, splitter=splitter,
            random_state=random_state,
        )
