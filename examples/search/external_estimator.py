"""
Third-party estimators with DistGridSearchCV (counterpart of the
reference's examples/search/xgb.py, which tuned XGBoost's sklearn
wrapper over Spark — 54 hyperparameter sets in parallel).

Any estimator speaking the sklearn fit/predict/get_params protocol
works on the generic fan-out path with zero adapter code — here
sklearn's HistGradientBoostingClassifier stands in for xgboost (same
sequential-boosting shape: you distribute the hyperparameter × fold
grid, not the trees). ``fit_params`` pass through end-to-end, with
array-valued ones (``sample_weight``) sliced to each train fold.

Sample output (CPU backend, this repo's test rig):
    -- Grid Search --
    Best Score: 0.9695
    Best learning_rate: 0.1
    Best max_depth: 4
    Best max_iter: 100
    -- weighted refit degrades class-0 holdout recall to 0.000 (by design)

Run: python examples/search/external_estimator.py
"""


import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np
from sklearn.datasets import load_digits
from sklearn.ensemble import HistGradientBoostingClassifier
from sklearn.metrics import recall_score
from sklearn.model_selection import train_test_split

from skdist_tpu.distribute.search import DistGridSearchCV


def main():
    X, y = load_digits(return_X_y=True)
    X = X.astype(np.float32)
    X_train, X_test, y_train, y_test = train_test_split(
        X, y, test_size=0.2, random_state=0
    )

    grid = {
        "learning_rate": [0.05, 0.1],
        "max_depth": [4, 6],
        "max_iter": [50, 100],
    }
    gs = DistGridSearchCV(
        HistGradientBoostingClassifier(random_state=0),
        grid, cv=3, scoring="f1_weighted",
    ).fit(X_train, y_train)
    print("-- Grid Search --")
    print(f"Best Score: {gs.best_score_:.4f}")
    for key in sorted(gs.best_params_):
        print(f"Best {key}: {gs.best_params_[key]}")

    # fit_params pass-through: a FULL-LENGTH sample_weight is sliced to
    # each train fold on every task (reference _index_param_value
    # semantics). Zero-weighting class 0 makes every candidate ignore it.
    w = np.where(y_train == 0, 0.0, 1.0)
    gs_w = DistGridSearchCV(
        HistGradientBoostingClassifier(random_state=0, max_iter=50),
        {"learning_rate": [0.1]}, cv=3, scoring="f1_weighted",
    ).fit(X_train, y_train, sample_weight=w)
    rec0 = recall_score(
        y_test, gs_w.predict(X_test), labels=[0], average="macro"
    )
    print(f"-- weighted refit degrades class-0 holdout recall to "
          f"{rec0:.3f} (by design)")


if __name__ == "__main__":
    main()
