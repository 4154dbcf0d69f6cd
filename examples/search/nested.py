"""
Nested meta-estimators (counterpart of the reference's
examples/search/nested.py): a one-vs-rest classifier whose base
estimator is itself a distributed grid search — each binary
sub-problem gets its own hyperparameter tuning, and the nested
search unwraps to its best estimator post-fit.

Sample output (CPU backend):
    -- OvR over nested grid search: holdout f1_weighted 0.9582

Run: python examples/search/nested.py
"""


import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np
from sklearn.datasets import load_digits
from sklearn.metrics import f1_score
from sklearn.model_selection import train_test_split

from skdist_tpu.distribute.multiclass import DistOneVsRestClassifier
from skdist_tpu.distribute.search import DistGridSearchCV
from skdist_tpu.models import LogisticRegression


def main():
    X, y = load_digits(return_X_y=True)
    X = (X / 16.0).astype(np.float32)
    X_train, X_test, y_train, y_test = train_test_split(
        X, y, test_size=0.2, random_state=0
    )

    inner = DistGridSearchCV(
        LogisticRegression(max_iter=60), {"C": [0.01, 0.1, 1.0, 10.0]},
        cv=3, scoring="accuracy",
    )
    ovr = DistOneVsRestClassifier(inner).fit(X_train, y_train)
    f1 = f1_score(y_test, ovr.predict(X_test), average="weighted")
    print(f"-- OvR over nested grid search: holdout f1_weighted {f1:.4f}")
    # each binary estimator kept its nested search's cv_results_
    per_class_c = [
        e.cv_results_["params"][
            int(np.argmin([int(r) for r in e.cv_results_["rank_test_score"]]))
        ]
        for e in ovr.estimators_
    ]
    print(f"-- per-class best params (first 3): {per_class_c[:3]}")


if __name__ == "__main__":
    main()
