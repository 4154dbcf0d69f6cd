"""
Parallel feature elimination (counterpart of the reference's
examples/eliminate/basic_usage.py: synthetic data with junk features,
~46x faster than sklearn RFECV on a Spark cluster; here all
(feature_set x fold) fits run as one vmapped program with column
masks riding the task axis).

Sample output (CPU backend):
    -- 9 feature sets x 5 folds in 8.45s
    -- best score 0.9954 with 20 features
    -- informative kept: 12/12, junk kept: 8/28

Run: python examples/eliminate/basic_usage.py
"""


import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import time

import numpy as np

from skdist_tpu.distribute.eliminate import DistFeatureEliminator
from skdist_tpu.models import LogisticRegression


def main():
    rng = np.random.RandomState(5)
    n, d_informative, d_junk = 5000, 12, 28
    y = rng.randint(0, 2, size=n)
    X_inf = y[:, None] * 1.5 + rng.normal(size=(n, d_informative))
    X_junk = rng.normal(size=(n, d_junk))
    X = np.hstack([X_junk[:, :14], X_inf, X_junk[:, 14:]]).astype(np.float32)
    informative = set(range(14, 14 + d_informative))

    start = time.time()
    fe = DistFeatureEliminator(
        LogisticRegression(max_iter=60),
        min_features_to_select=8, step=4, cv=5, scoring="accuracy",
    ).fit(X, y)
    wall = time.time() - start

    kept = set(fe.best_features_)
    print(f"-- {len(fe.scores_)} feature sets x 5 folds in {wall:.2f}s")
    print(f"-- best score {fe.best_score_:.4f} with {fe.n_features_} features")
    print(f"-- informative kept: {len(kept & informative)}/{d_informative}, "
          f"junk kept: {len(kept - informative)}/{d_junk}")


if __name__ == "__main__":
    main()
