"""
Multi-model search (counterpart of the reference's
examples/search/multimodel.py): heterogeneous model families, n
sampled param sets each, winner refit.

Sample output (CPU backend):
    -- winner: lr {'C': 100.0}
    -- best CV accuracy 0.9715 (worst candidate 0.9241)
    -- holdout accuracy 0.9611

Run: python examples/search/multimodel.py
"""


import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np
from sklearn.datasets import load_digits
from sklearn.model_selection import train_test_split

from skdist_tpu.distribute.search import DistMultiModelSearch
from skdist_tpu.models import (
    LogisticRegression,
    RandomForestClassifier,
    RidgeClassifier,
)


def main():
    X, y = load_digits(return_X_y=True)
    X = (X / 16.0).astype(np.float32)
    X_train, X_test, y_train, y_test = train_test_split(
        X, y, test_size=0.2, random_state=0
    )

    models = [
        ("lr", LogisticRegression(max_iter=60),
         {"C": list(np.logspace(-2, 2, 10))}),
        ("ridge", RidgeClassifier(), {"alpha": [0.1, 1.0, 10.0]}),
        ("rf", RandomForestClassifier(n_estimators=32, random_state=0),
         {"max_depth": [6, 8], "max_features": ["sqrt", 0.5]}),
    ]
    mm = DistMultiModelSearch(
        models, n=4, cv=3, scoring="accuracy", random_state=0, verbose=1
    ).fit(X_train, y_train)

    print(f"-- winner: {mm.best_model_name_} {mm.best_params_}")
    print(f"-- best CV accuracy {mm.best_score_:.4f} "
          f"(worst candidate {mm.worst_score_:.4f})")
    print(f"-- holdout accuracy {np.mean(mm.predict(X_test) == y_test):.4f}")


if __name__ == "__main__":
    main()
