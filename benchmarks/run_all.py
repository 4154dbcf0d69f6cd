"""
The five BASELINE.json configs, shape-faithful and zero-egress.

Each config prints one JSON line: `{"config": ..., "value": ...,
"unit": ..., "device": {...}, ...}` with cold/warm walls and, where
cheap, an sklearn reference engine time. All configs run in ONE process
on whatever ``jax.devices()`` gives, and every line names that device;
at full scale the values are chip numbers, so without a TPU the command
exits non-zero instead of printing them for a CPU (a reduced ``--scale``
is the code-path smoke and runs anywhere, with no MFU off the chip).
Real datasets are not fetchable here, so every workload matches the
named dataset's shape:

1. DistGridSearchCV(LogisticRegression) on 20news shape (11314x4096,
   20 classes, 96 C's x 5 folds) — also bench.py's headline.
2. DistRandomizedSearchCV(SGDClassifier) on covtype shape
   (n x 54, 7 classes), n_iter=60, 5 folds.
3. DistOneVsRestClassifier(LinearSVC) on 20news shape, 20 classes.
4. DistRandomForestClassifier(n_estimators=256) on a HIGGS-shaped
   subset (n x 28, binary).
5. batch_predict predict_proba over 1M rows (the pandas-UDF analogue).

Usage:
    python benchmarks/run_all.py [--scale 0.05] [--config N] [--ref]

--scale shrinks row counts (CPU smoke: --scale 0.02); --ref also times
the sklearn/joblib engine on the same workload.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def _emit(payload):
    print(json.dumps({**payload, "device": device_fields()}), flush=True)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return time.perf_counter() - t0, out


def _text_width(scale):
    """Feature width for the text-shaped configs. Row scaling alone
    keeps the faithful d=4096; only deep smoke scales (< 0.2) shrink
    the feature dimension too, with a loud notice — a silently
    changed d would make fits/sec incomparable to BASELINE."""
    if scale >= 0.2:
        return 4096
    print("[run_all] smoke scale: text feature width reduced to 512 "
          "(results not comparable to BASELINE shapes)", file=sys.stderr)
    return 512


from bench import device_fields, make_tabular  # shared with bench.py


def config_1_gridsearch(scale, ref):
    from bench import make_20news_shaped
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.parallel import TPUBackend

    n = max(500, int(11314 * scale))
    d = _text_width(scale)
    X, y = make_20news_shaped(n=n, d=d, k=20)
    grid = {"C": list(np.logspace(-3, 2, 96))}

    def run():
        return DistGridSearchCV(
            LogisticRegression(max_iter=30, tol=1e-4), grid,
            backend=TPUBackend(reuse_broadcast=True), cv=5, scoring="accuracy",
        ).fit(X, y)

    cold, _ = _timed(run)
    warm, gs = _timed(run)
    from bench import _F32_HIGHEST_PASSES, lbfgs_fit_flops, mfu_fields

    flops = lbfgs_fit_flops(int(0.8 * n), d, 20, 30) * 480
    out = {
        "config": "1: GridSearchCV LogReg 20news-shaped 96x5",
        "shape": [n, d, 20], "cold_s": round(cold, 2),
        "warm_s": round(warm, 2),
        "value": round(480 / warm, 2), "unit": "fits/sec",
        "best_score": float(gs.best_score_),
        **mfu_fields(flops / warm / 1e12, passes=_F32_HIGHEST_PASSES,
                     basis="n_iter assumed = max_iter = 30",
                     device=device_fields()),
    }
    if ref:
        from sklearn.linear_model import LogisticRegression as SkLR
        from sklearn.model_selection import GridSearchCV

        sk_s, _ = _timed(lambda: GridSearchCV(
            SkLR(max_iter=30, tol=1e-4), {"C": grid["C"][:8]}, cv=5,
            n_jobs=-1,
        ).fit(X, y))
        # scale the 8-candidate joblib run up to the 96-candidate grid
        out["sklearn_joblib_est_s"] = round(sk_s * 96 / 8, 1)
    _emit(out)


def config_2_randomized_sgd(scale, ref):
    from skdist_tpu.distribute.search import DistRandomizedSearchCV
    from skdist_tpu.models import SGDClassifier
    from skdist_tpu.parallel import TPUBackend

    n = max(2000, int(100_000 * scale))
    X, y = make_tabular(n, 54, 7, seed=1)
    dists = {"alpha": list(np.logspace(-6, -2, 60))}

    def run():
        return DistRandomizedSearchCV(
            SGDClassifier(max_iter=20, random_state=0), dists, n_iter=60,
            backend=TPUBackend(reuse_broadcast=True), cv=5, scoring="accuracy", random_state=0,
        ).fit(X, y)

    cold, _ = _timed(run)
    warm, rs = _timed(run)
    out = {
        "config": "2: RandomizedSearchCV SGD covtype-shaped n_iter=60",
        "shape": [n, 54, 7], "cold_s": round(cold, 2),
        "warm_s": round(warm, 2),
        "value": round(300 / warm, 2), "unit": "fits/sec",
        "best_score": float(rs.best_score_),
    }
    if ref:
        from sklearn.linear_model import SGDClassifier as SkSGD
        from sklearn.model_selection import RandomizedSearchCV

        sk_s, _ = _timed(lambda: RandomizedSearchCV(
            SkSGD(max_iter=20, random_state=0), dists, n_iter=10, cv=5,
            n_jobs=-1, random_state=0,
        ).fit(X, y))
        out["sklearn_joblib_est_s"] = round(sk_s * 60 / 10, 1)
    _emit(out)


def config_3_ovr_svc(scale, ref):
    from bench import make_20news_shaped
    from skdist_tpu.distribute.multiclass import DistOneVsRestClassifier
    from skdist_tpu.models import LinearSVC
    from skdist_tpu.parallel import TPUBackend

    n = max(500, int(11314 * scale))
    d = _text_width(scale)
    X, y = make_20news_shaped(n=n, d=d, k=20)

    def run():
        return DistOneVsRestClassifier(
            LinearSVC(C=1.0, max_iter=100), backend=TPUBackend(reuse_broadcast=True),
        ).fit(X, y)

    cold, _ = _timed(run)
    warm, ovr = _timed(run)
    acc = float(np.mean(ovr.predict(X) == y))
    out = {
        "config": "3: OneVsRest LinearSVC 20news-shaped 20-class",
        "shape": [n, d, 20], "cold_s": round(cold, 2),
        "warm_s": round(warm, 2),
        "value": round(20 / warm, 2), "unit": "binary fits/sec",
        "train_acc": acc,
    }
    if ref:
        from sklearn.multiclass import OneVsRestClassifier
        from sklearn.svm import LinearSVC as SkSVC

        # iteration budget matched to the estimator under test
        sk_s, _ = _timed(lambda: OneVsRestClassifier(
            SkSVC(C=1.0, max_iter=100), n_jobs=-1,
        ).fit(X, y))
        out["sklearn_joblib_s"] = round(sk_s, 1)
    _emit(out)


def config_4_forest(scale, ref):
    from skdist_tpu.distribute.ensemble import DistRandomForestClassifier
    from skdist_tpu.parallel import TPUBackend

    n = max(2000, int(200_000 * scale))
    X, y = make_tabular(n, 28, 2, seed=2)

    def run():
        return DistRandomForestClassifier(
            n_estimators=256, max_depth=8, random_state=0,
            backend=TPUBackend(reuse_broadcast=True),
        ).fit(X, y)

    cold, _ = _timed(run)
    warm, rf = _timed(run)
    acc = float(np.mean(rf.predict(X) == y))
    out = {
        "config": "4: RandomForest 256 trees HIGGS-shaped",
        "shape": [n, 28, 2], "cold_s": round(cold, 2),
        "warm_s": round(warm, 2),
        "value": round(256 / warm, 2), "unit": "trees/sec",
        "train_acc": acc,
    }
    from bench import forest_tree_flops, mfu_fields
    from skdist_tpu.models.tree import resolve_hist_config

    mode, _blk = resolve_hist_config(28, 32)
    out["hist_mode"] = mode
    if mode in ("matmul", "matmul_sib", "pallas"):
        # binary classification: channels = 2 classes + count = 3; the
        # one-hot contraction operands are exact at default (1-pass)
        # matmul precision, so peak is the full bf16 number
        flops = forest_tree_flops(n, 28, 32, 3, 8) * 256
        if mode == "matmul_sib":
            # sibling subtraction executes the root level in full and
            # half of every deeper level's contraction: the MFU basis
            # counts FLOPs actually run, not the full-level model
            D = 8
            flops *= (1.0 + (2.0**D - 2.0) / 2.0) / (2.0**D - 1.0)
        out.update(mfu_fields(flops / warm / 1e12, passes=1,
                              basis=f"hist_mode={mode}, depth 8",
                              device=device_fields()))
    if ref:
        from sklearn.ensemble import RandomForestClassifier as SkRF

        sk_s, _ = _timed(lambda: SkRF(
            n_estimators=256, max_depth=8, n_jobs=-1, random_state=0,
        ).fit(X, y))
        out["sklearn_joblib_s"] = round(sk_s, 1)
    _emit(out)


def config5_recipe(scale):
    """The ONE dataset/model recipe for the 1M-row prediction
    workload, shared by the offline config (below) and the serving
    bench (``benchmarks/bench_serving.py``) so their numbers describe
    the same model and row distribution: 10-class LogisticRegression
    on 64 dense features, uniform-random scoring rows.

    Returns ``(model, Xs, (X, y))`` with ``Xs`` scaled from the
    faithful 1M and ``(X, y)`` the training split (for sklearn
    reference refits).
    """
    from skdist_tpu.models import LogisticRegression

    n_train = 5000
    n_score = max(10_000, int(1_000_000 * scale))
    X, y = make_tabular(n_train, 64, 10, seed=3)
    model = LogisticRegression(max_iter=40).fit(X, y)
    Xs = np.random.RandomState(4).rand(n_score, 64).astype(np.float32)
    return model, Xs, (X, y)


def config_5_batch_predict(scale, ref):
    from skdist_tpu.distribute.predict import batch_predict
    from skdist_tpu.parallel import TPUBackend

    model, Xs, (X, y) = config5_recipe(scale)
    n_score = Xs.shape[0]

    def run():
        return batch_predict(
            model, Xs, method="predict_proba", backend=TPUBackend(reuse_broadcast=True),
        )

    cold, _ = _timed(run)
    warm, proba = _timed(run)
    out = {
        "config": "5: batch predict_proba 1M-row-shaped",
        "rows": n_score, "cold_s": round(cold, 2),
        "warm_s": round(warm, 3),
        "value": round(n_score / warm), "unit": "rows/sec",
        "proba_shape": list(proba.shape),
    }
    if ref:
        from sklearn.linear_model import LogisticRegression as SkLR

        sk = SkLR(max_iter=40).fit(X, y)
        sk_s, _ = _timed(lambda: sk.predict_proba(Xs))
        out["sklearn_s"] = round(sk_s, 3)
    _emit(out)


CONFIGS = {
    1: config_1_gridsearch,
    2: config_2_randomized_sgd,
    3: config_3_ovr_svc,
    4: config_4_forest,
    5: config_5_batch_predict,
}


def _quality_tail(data_dir):
    """Quality-parity table vs BASELINE.md (builtin digits /
    breast-cancer rows always; covtype / 20news rows when ``data_dir``
    holds them, clean skip otherwise)."""
    import quality_parity

    quality_parity.run_rows(data_dir)
    quality_parity.print_table()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0,
                    help="row-count multiplier (use ~0.02 for CPU smoke)")
    ap.add_argument("--config", type=int, default=None,
                    help="run one config (1-5) instead of all")
    ap.add_argument("--ref", action="store_true",
                    help="also time the sklearn/joblib engine")
    ap.add_argument("--data-dir", default=None,
                    help="real-dataset hook: an "
                         "sklearn data_home holding covtype/20news; "
                         "runs benchmarks/quality_parity.py after the "
                         "configs so the suite ends with a quality "
                         "table vs BASELINE.md (clean skip per row "
                         "when data is absent)")
    ap.add_argument("--quality", action="store_true",
                    help="run ONLY the quality-parity table")
    args = ap.parse_args()

    if args.quality:
        _quality_tail(args.data_dir)
        return

    device = device_fields()
    if args.scale >= 1.0 and device["platform"] != "tpu":
        raise SystemExit(
            "[run_all] full-scale values are chip measurements and this "
            f"process has {device}; run on a TPU, or pass a reduced "
            "--scale for the code-path smoke"
        )
    for idx in [args.config] if args.config else sorted(CONFIGS):
        CONFIGS[idx](args.scale, args.ref)
    if args.data_dir:
        # real-data quality tail: ends the suite with the parity table
        _quality_tail(args.data_dir)


if __name__ == "__main__":
    main()
