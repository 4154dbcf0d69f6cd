"""
Distributed grid search on the hand-written digits dataset
(counterpart of the reference's examples/search/basic_usage.py and
hand_written_digits.py, which ran 750 SVC fits on a 640-core Spark
cluster — here the whole grid batches into vmapped XLA programs).

Sample output (CPU backend, this repo's test rig):
    -- 200 fits in 25.54s (7.8 fits/sec)
    -- best params: {'C': 29.76, 'tol': 0.0001}
    -- best CV f1_weighted: 0.9730
    -- holdout f1_weighted: 0.9638
    -- pickle round-trip OK (10151 bytes)

Run: python examples/search/basic_usage.py
"""


import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import pickle
import time

import numpy as np
from sklearn.datasets import load_digits
from sklearn.model_selection import train_test_split
from sklearn.metrics import f1_score

from skdist_tpu.distribute.search import DistGridSearchCV
from skdist_tpu.models import LogisticRegression


def main():
    X, y = load_digits(return_X_y=True)
    X = (X / 16.0).astype(np.float32)
    X_train, X_test, y_train, y_test = train_test_split(
        X, y, test_size=0.2, random_state=0
    )

    grid = {"C": list(np.logspace(-3, 2, 20)), "tol": [1e-4, 1e-3]}
    n_fits = 40 * 5

    start = time.time()
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=60),
        grid, backend=None,  # backend="tpu" on TPU hosts
        cv=5, scoring="f1_weighted", verbose=1,
    ).fit(X_train, y_train)
    wall = time.time() - start

    print(f"-- {n_fits} fits in {wall:.2f}s ({n_fits / wall:.1f} fits/sec)")
    print(f"-- best params: {gs.best_params_}")
    print(f"-- best CV f1_weighted: {gs.best_score_:.4f}")
    preds = gs.predict(X_test)
    print(f"-- holdout f1_weighted: {f1_score(y_test, preds, average='weighted'):.4f}")

    # fitted artifact is a plain picklable object (no backend inside)
    blob = pickle.dumps(gs)
    loaded = pickle.loads(blob)
    assert (loaded.predict(X_test) == preds).all()
    print(f"-- pickle round-trip OK ({len(blob)} bytes)")


if __name__ == "__main__":
    main()
