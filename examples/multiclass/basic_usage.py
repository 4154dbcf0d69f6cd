"""
One-vs-rest vs one-vs-one on digits (counterpart of the reference's
examples/multiclass/basic_usage.py, which reported OvR 0.9589 vs OvO
0.9805 weighted F1).

Sample output (CPU backend):
    -- OvR (10 binary fits, one program): f1_weighted 0.9610
    -- OvO (45 pair fits, one program):   f1_weighted 0.9778

Run: python examples/multiclass/basic_usage.py
"""


import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np
from sklearn.datasets import load_digits
from sklearn.metrics import f1_score
from sklearn.model_selection import train_test_split

from skdist_tpu.distribute.multiclass import (
    DistOneVsOneClassifier,
    DistOneVsRestClassifier,
)
from skdist_tpu.models import LinearSVC


def main():
    X, y = load_digits(return_X_y=True)
    X = (X / 16.0).astype(np.float32)
    X_train, X_test, y_train, y_test = train_test_split(
        X, y, test_size=0.2, random_state=0
    )

    ovr = DistOneVsRestClassifier(LinearSVC(C=1.0, max_iter=300)).fit(
        X_train, y_train
    )
    f1_ovr = f1_score(y_test, ovr.predict(X_test), average="weighted")
    print(f"-- OvR (10 binary fits, one program): f1_weighted {f1_ovr:.4f}")

    ovo = DistOneVsOneClassifier(LinearSVC(C=1.0, max_iter=300)).fit(
        X_train, y_train
    )
    f1_ovo = f1_score(y_test, ovo.predict(X_test), average="weighted")
    print(f"-- OvO (45 pair fits, one program):   f1_weighted {f1_ovo:.4f}")


if __name__ == "__main__":
    main()
