"""
Hand-written digits: 750 fits as a handful of XLA programs
(counterpart of the reference's examples/search/hand_written_digits.py,
which ran 750 SVC fits in 1.45 s wall against 7.3 min of total task
time on a 640-core Spark cluster — a ~300x parallel-efficiency claim).

Here the same fit count rides the task axis of ONE compiled program:
150 C values × 5 folds of logistic regression on the sklearn-bundled
digits set. The "cluster" is whatever mesh the backend sees — the
parallel-efficiency ratio is (total serial fit time) / wall.

The full 150-candidate grid is the accelerator workload; on a CPU the
grid shrinks to 30 candidates (marked in the output) so the example
stays interactive.

Sample output (CPU, 30-candidate grid):
    Train time: 21.04s for 150 fits (7.1 fits/sec) [cpu grid]
    Best score: 0.9277
    -- top CV results --
        param_C  mean_test_score
    18   0.5298           0.9277
    17   0.3290           0.9271
    19   0.8532           0.9271

Run: python examples/search/hand_written_digits.py
"""


import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np
import pandas as pd
from sklearn.datasets import load_digits

from skdist_tpu.distribute.search import DistGridSearchCV
from skdist_tpu.models import LogisticRegression


def main():
    X, y = load_digits(return_X_y=True)
    X = (X / 16.0).astype(np.float32)

    import jax

    on_accel = jax.default_backend() != "cpu"
    n_cand = 150 if on_accel else 30
    tag = "" if on_accel else " [cpu grid]"
    grid = {"C": list(np.logspace(-4, 2, n_cand))}
    n_fits = n_cand * 5
    t0 = time.time()
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=50, tol=1e-3),
        grid, cv=5, scoring="accuracy",
    ).fit(X, y)
    wall = time.time() - t0
    print(f"Train time: {wall:.2f}s for {n_fits} fits "
          f"({n_fits / wall:.1f} fits/sec){tag}")
    print(f"Best score: {gs.best_score_:.4f}")

    df = pd.DataFrame({
        "param_C": np.round(np.asarray(
            gs.cv_results_["param_C"], dtype=float), 4),
        "mean_test_score": np.round(
            gs.cv_results_["mean_test_score"], 4),
    }).sort_values("mean_test_score", ascending=False)
    print("-- top CV results --")
    print(df.head(3).to_string())


if __name__ == "__main__":
    main()
