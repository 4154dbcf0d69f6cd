"""
Quality-parity table vs the reference's published model-quality rows
(round-4 VERDICT task 5). BASELINE.md rows 1, 2, 9, 10, 11 are the
reference's author-recorded scores on REAL datasets; this command
reproduces each protocol with skdist_tpu estimators and prints a
side-by-side table.

Two tiers:

- **builtin** (always run): digits OvR/OvO weighted F1 (reference
  ``examples/multiclass/basic_usage.py:38-60``: split 80/20 at
  random_state=10, LogisticRegression) and breast-cancer grid-search
  best ROC AUC (reference ``examples/search/basic_usage.py:27-29``:
  C in 1e-3..1e2, cv=5, roc_auc). These datasets ship inside sklearn,
  so the parity table is never empty even in a zero-egress
  environment.
- **fetched** (run when ``--data-dir`` holds the data, clean skip
  otherwise): covtype LR grid CV/holdout-F1 and RF-100 holdout-F1
  (reference ``examples/search/spark_ml.py:30-36``: split 80/20 at
  random_state=4, StandardScaler, C in {10,1,0.1,0.01}, cv=5,
  f1_weighted) and the 20newsgroups Encoderizer small/medium/large
  best-CV-f1 triple (reference ``examples/encoder/basic_usage.py:
  20-26``: first 1000 docs, C in {0.1,1,10}, cv=5). ``--data-dir`` is
  passed to sklearn's fetchers as ``data_home`` with
  ``download_if_missing=False`` — point it at any scikit_learn_data
  cache that already holds covtype / 20news.

Usage:
    python benchmarks/quality_parity.py [--data-dir DIR]
        [--covtype-rows N] [--skip-builtin]

``--covtype-rows`` subsamples covtype for smoke runs (the full 581k-row
protocol is the comparable one; subsampled runs are labeled).
Each row also prints as a JSON line for the capture logs.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def _emit(row):
    print(json.dumps({"quality_row": row}), flush=True)


ROWS = []


def add_row(name, ours, ref, note=""):
    row = {
        "row": name,
        "ours": None if ours is None else round(float(ours), 4),
        "reference": ref,
        "delta": None if ours is None else round(float(ours) - ref, 4),
        "note": note,
    }
    ROWS.append(row)
    _emit(row)


def skip_row(name, why):
    ROWS.append({"row": name, "ours": None, "reference": None,
                 "delta": None, "note": f"skipped: {why}"})


def add_noncomparable_row(name, ours, ref, note=""):
    """A real-data row whose protocol deviates from the published one
    (subsampled rows, fewer estimators): the reference number is
    context, not a comparison — delta stays None so the readout never
    reads as a quality regression."""
    row = {
        "row": name,
        "ours": None if ours is None else round(float(ours), 4),
        "reference": ref,
        "delta": None,
        "note": f"modified protocol (not comparable to ref); {note}".rstrip("; "),
    }
    ROWS.append(row)
    _emit(row)


def add_synth_row(name, ours, ref, note=""):
    """A synthetic-stand-in row: the PROTOCOL ran and produced a score,
    but the data is generated, so the published reference number is
    context, not a comparison — delta stays None."""
    row = {
        "row": name,
        "ours": None if ours is None else round(float(ours), 4),
        "reference": ref,
        "delta": None,
        "note": f"synthetic stand-in (not comparable to ref); {note}".rstrip("; "),
    }
    ROWS.append(row)
    _emit(row)


# ------------------------------------------------- synthetic stand-ins
# Cached generated datasets for the fetched rows (VERDICT weak #5): in
# zero-egress environments the covtype/20news protocols RUN on shaped
# synthetic data instead of skipping, so the harness (and its CI
# smoke) always exercises the full pipeline — scaling, grids, the
# Encoderizer text path, the sparse fit plane. Scores are protocol
# health signals, not reference comparisons.
_SYNTH_CACHE = {}


def _synthetic_covtype(n_rows=2500, seed=0):
    """Covtype-shaped stand-in: 54 features, 7 classes, labels 1..7."""
    key = ("covtype", n_rows, seed)
    if key not in _SYNTH_CACHE:
        from bench import make_tabular

        X, y = make_tabular(n_rows, 54, 7, seed=seed)
        _SYNTH_CACHE[key] = (X, y + 1)
    return _SYNTH_CACHE[key]


def _synthetic_20news_docs(n_docs=1000, seed=1, k=20):
    """20news-shaped stand-in: synthetic documents over a zipf
    vocabulary with class-specific topic tokens, so the Encoderizer's
    text featurisers have real signal to find."""
    key = ("20news", n_docs, seed, k)
    if key not in _SYNTH_CACHE:
        rng = np.random.RandomState(seed)
        vocab_size = 4000
        common = 1.0 / np.arange(1, vocab_size + 1, dtype=np.float64)
        common /= common.sum()
        cum = np.cumsum(common)
        topic_words = rng.choice(
            vocab_size, size=(k, 25), replace=True
        )
        docs, labels = [], []
        for i in range(n_docs):
            c = i % k
            n_tok = int(rng.randint(30, 120))
            toks = np.searchsorted(cum, rng.rand(n_tok))
            n_topic = max(4, n_tok // 5)
            toks[:n_topic] = topic_words[c][
                rng.randint(0, topic_words.shape[1], size=n_topic)
            ]
            docs.append(" ".join(f"w{t}" for t in toks))
            labels.append(c)
        _SYNTH_CACHE[key] = (docs, np.asarray(labels))
    return _SYNTH_CACHE[key]


# ----------------------------------------------------------------- builtin
def run_digits():
    """BASELINE row 10: OvR 0.9589 / OvO 0.9805 weighted F1 on digits."""
    from sklearn.datasets import load_digits
    from sklearn.metrics import f1_score
    from sklearn.model_selection import train_test_split

    from skdist_tpu.distribute.multiclass import (
        DistOneVsOneClassifier,
        DistOneVsRestClassifier,
    )
    from skdist_tpu.models import LogisticRegression

    data = load_digits()
    X_train, X_test, y_train, y_test = train_test_split(
        data["data"], data["target"], test_size=0.2, random_state=10
    )
    ovr = DistOneVsRestClassifier(
        LogisticRegression(max_iter=100)
    ).fit(X_train, y_train)
    add_row(
        "digits OvR weighted F1",
        f1_score(y_test, ovr.predict(X_test), average="weighted"),
        0.9589,
    )
    ovo = DistOneVsOneClassifier(
        LogisticRegression(max_iter=100)
    ).fit(X_train, y_train)
    add_row(
        "digits OvO weighted F1",
        f1_score(y_test, ovo.predict(X_test), average="weighted"),
        0.9805,
    )


def run_breast_cancer():
    """BASELINE row 11: grid-search best ROC AUC 0.99253 (C=1.0)."""
    from sklearn.datasets import load_breast_cancer

    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression

    data = load_breast_cancer()
    # max_iter=1000: breast-cancer ships unscaled (feature ranges to
    # ~4e3), where L-BFGS converges slowly; the reference's liblinear
    # coordinate solver needed only its default budget. Quality parity
    # is about the converged model, not the iteration count.
    model = DistGridSearchCV(
        LogisticRegression(max_iter=1000),
        {"C": [0.001, 0.01, 0.1, 1.0, 10.0, 100.0]},
        cv=5, scoring="roc_auc",
    ).fit(data["data"], data["target"])
    add_row(
        "breast-cancer grid best ROC AUC",
        model.best_score_, 0.99253,
        note=f"best C={model.best_params_['C']}",
    )


# ----------------------------------------------------------------- fetched
def run_covtype(data_dir, n_rows=None, rf_estimators=100):
    """BASELINE rows 1-2: LR grid CV 0.7148 / holdout F1 0.7118;
    RF-100 holdout F1 0.9537. Without a local covtype cache the SAME
    protocol runs on the cached covtype-shaped synthetic stand-in
    (rows emitted via :func:`add_synth_row`) instead of skipping."""
    from sklearn.datasets import fetch_covtype

    synthetic = False
    try:
        data = fetch_covtype(data_home=data_dir, download_if_missing=False)
    except OSError:
        synthetic = True
    from sklearn.metrics import f1_score
    from sklearn.model_selection import train_test_split
    from sklearn.preprocessing import StandardScaler

    from skdist_tpu.distribute.ensemble import DistRandomForestClassifier
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression

    if synthetic:
        X, y = _synthetic_covtype(n_rows or 2500)
        note = f"covtype-shaped synthetic, {len(y)} rows"
        emit = add_synth_row
    else:
        X, y = data["data"], data["target"]
        note = "full 581k-row protocol"
        emit = add_row
        if n_rows is not None and n_rows < len(y):
            keep = np.random.RandomState(0).choice(
                len(y), size=n_rows, replace=False
            )
            X, y = X[keep], y[keep]
            note = f"subsampled to {n_rows} rows"
            emit = add_noncomparable_row
    X_train, X_test, y_train, y_test = train_test_split(
        X, y, test_size=0.2, random_state=4
    )
    scaler = StandardScaler()
    X_train = scaler.fit_transform(X_train).astype(np.float32)
    X_test = scaler.transform(X_test).astype(np.float32)

    t0 = time.time()
    lr = DistGridSearchCV(
        LogisticRegression(max_iter=100),
        {"C": [10.0, 1.0, 0.1, 0.01]}, cv=5, scoring="f1_weighted",
    ).fit(X_train, y_train)
    lr_wall = time.time() - t0
    emit("covtype LR grid best CV f1_weighted", lr.best_score_,
         0.7148, note=f"{note}; train {lr_wall:.1f}s (ref 85.7s)")
    emit(
        "covtype LR holdout weighted F1",
        f1_score(y_test, lr.predict(X_test), average="weighted"),
        0.7118, note=note,
    )

    t0 = time.time()
    rf = DistRandomForestClassifier(
        n_estimators=rf_estimators, random_state=0
    ).fit(X_train, y_train)
    rf_wall = time.time() - t0
    # the 0.9537 reference is RF-100: a smaller forest on real data
    # must not bill its score against it
    rf_emit = emit if rf_estimators == 100 else add_noncomparable_row
    if synthetic:
        rf_emit = emit
    rf_emit(
        f"covtype RF-{rf_estimators} holdout weighted F1",
        f1_score(y_test, rf.predict(X_test), average="weighted"),
        0.9537, note=f"{note}; train {rf_wall:.1f}s (ref 9.2s)",
    )


def run_encoder_20news(data_dir, sizes=("small", "medium", "large"),
                       n_docs=1000):
    """BASELINE row 9: Encoderizer small/medium/large best CV f1 on the
    first 1000 20newsgroups docs: 0.3795 / 0.4671 / 0.4503. Without a
    local 20news cache the SAME protocol runs on the cached synthetic
    document stand-in instead of skipping."""
    from sklearn.datasets import fetch_20newsgroups

    synthetic = False
    try:
        dataset = fetch_20newsgroups(
            data_home=data_dir, shuffle=True, random_state=1,
            remove=("headers", "footers", "quotes"),
            download_if_missing=False,
        )
    except OSError:
        synthetic = True
    import pandas as pd

    from skdist_tpu.distribute.encoder import Encoderizer
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression

    if synthetic:
        docs, y = _synthetic_20news_docs(n_docs)
        df = pd.DataFrame({"text": docs})
        emit, extra = add_synth_row, f"{len(y)} synthetic docs"
    else:
        df = pd.DataFrame({"text": dataset["data"]})[:n_docs]
        y = dataset["target"][:n_docs]
        emit, extra = add_row, ""
        if n_docs != 1000:
            # the published numbers are for the first 1000 docs
            emit, extra = add_noncomparable_row, f"first {n_docs} docs"
    targets = {"small": 0.3795, "medium": 0.4671, "large": 0.4503}
    for size in sizes:
        ref = targets[size]
        # fit_transform WITHOUT y, exactly as the reference protocol
        # does (`encoder/basic_usage.py:57-58`: the Encoderizer is fit
        # unsupervised there)
        X_t = Encoderizer(size=size).fit_transform(df)
        model = DistGridSearchCV(
            LogisticRegression(max_iter=100),
            {"C": [0.1, 1.0, 10.0]}, cv=5, scoring="f1_weighted",
        ).fit(X_t, y)
        emit(f"20news Encoderizer[{size}] best CV f1_weighted",
             model.best_score_, ref, note=extra)


def run_rows(data_dir=None, covtype_rows=None, skip_builtin=False):
    ROWS.clear()
    if not skip_builtin:
        run_digits()
        run_breast_cancer()
    run_covtype(data_dir, n_rows=covtype_rows)
    run_encoder_20news(data_dir)
    return ROWS


def print_table(rows=None):
    rows = ROWS if rows is None else rows
    width = max(len(r["row"]) for r in rows) + 2
    print("\n== quality parity vs reference (BASELINE.md) ==")
    print(f"{'row':<{width}}{'ours':>9}{'reference':>11}{'delta':>9}  note")
    for r in rows:
        ours = "-" if r["ours"] is None else f"{r['ours']:.4f}"
        ref = "-" if r["reference"] is None else f"{r['reference']:.4f}"
        delta = "-" if r["delta"] is None else f"{r['delta']:+.4f}"
        print(f"{r['row']:<{width}}{ours:>9}{ref:>11}{delta:>9}  {r['note']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data-dir", default=None,
                    help="sklearn data_home holding covtype / 20news "
                         "caches; fetched rows skip cleanly if absent")
    ap.add_argument("--covtype-rows", type=int, default=None,
                    help="subsample covtype for smoke runs (labeled)")
    ap.add_argument("--skip-builtin", action="store_true")
    args = ap.parse_args()

    import jax

    print(f"[quality_parity] device: {jax.devices()[0].platform} "
          f"{jax.devices()[0].device_kind} x{len(jax.devices())}",
          file=sys.stderr)
    run_rows(args.data_dir, covtype_rows=args.covtype_rows,
             skip_builtin=args.skip_builtin)
    print_table()


if __name__ == "__main__":
    main()
