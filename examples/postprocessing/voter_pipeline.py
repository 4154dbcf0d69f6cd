"""
Voting over independently-fitted distributed searches (counterpart of
the reference's examples/postprocessing/voter_pipeline.py: two grid
searches + a big ERT voted together, 26x parallel efficiency on a
32-core cluster).

Sample output (CPU backend; the ERT leg runs the host C engine):
    -- lr: holdout f1_weighted 0.9610
    -- lr_bal: holdout f1_weighted 0.9610
    -- ert: holdout f1_weighted 0.9723
    -- voter: holdout f1_weighted 0.9694

Run: python examples/postprocessing/voter_pipeline.py
"""


import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np
from sklearn.datasets import load_digits
from sklearn.metrics import f1_score
from sklearn.model_selection import train_test_split

from skdist_tpu.distribute.ensemble import DistExtraTreesClassifier
from skdist_tpu.distribute.search import DistGridSearchCV
from skdist_tpu.models import LogisticRegression
from skdist_tpu.postprocessing import SimpleVoter


def main():
    X, y = load_digits(return_X_y=True)
    X = (X / 16.0).astype(np.float32)
    X_train, X_test, y_train, y_test = train_test_split(
        X, y, test_size=0.2, random_state=0
    )

    gs1 = DistGridSearchCV(
        LogisticRegression(max_iter=60), {"C": [0.1, 1.0, 10.0]},
        cv=3, scoring="f1_weighted",
    ).fit(X_train, y_train)
    gs2 = DistGridSearchCV(
        LogisticRegression(max_iter=60, class_weight="balanced"),
        {"C": [0.1, 1.0, 10.0]}, cv=3, scoring="f1_weighted",
    ).fit(X_train, y_train)
    ert = DistExtraTreesClassifier(
        n_estimators=128, max_depth=8, random_state=0
    ).fit(X_train, y_train)

    voter = SimpleVoter(
        [("lr", gs1.best_estimator_), ("lr_bal", gs2.best_estimator_),
         ("ert", ert)],
        classes=gs1.best_estimator_.classes_, voting="soft",
    )
    for name, model in [("lr", gs1), ("lr_bal", gs2), ("ert", ert),
                        ("voter", voter)]:
        f1 = f1_score(y_test, model.predict(X_test), average="weighted")
        print(f"-- {name}: holdout f1_weighted {f1:.4f}")


if __name__ == "__main__":
    main()
