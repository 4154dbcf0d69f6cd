"""
Pipelines with DistGridSearchCV, two ways (counterpart of the
reference's examples/search/pipeline.py, which tuned a
TfidfVectorizer→TruncatedSVD→LogisticRegression pipeline over
20newsgroups on Spark):

1. a standard sklearn Pipeline as the BASE ESTIMATOR of
   DistGridSearchCV — pipelines are host-side estimators, so the
   search runs them on the generic fan-out path, tuning params of
   every step (``clf__C``, ``pca__n_components``);
2. DistGridSearchCV as the FINAL STEP of a Pipeline — the upstream
   transformers run once, the search distributes only the final
   estimator's candidates (here on the batched device path, since the
   final estimator is this package's LogisticRegression).

Zero-egress environment: 20newsgroups can't be fetched, so the demo
uses sklearn's bundled digits dataset with a scale→PCA front end
standing in for the tfidf→svd front end.

Sample output (CPU backend, this repo's test rig):
    -- Pipeline as base estimator: best CV f1_weighted 0.9624
    -- DistGridSearchCV as final pipeline step: best CV f1_weighted 0.9606
    -- holdout f1_weighted: 0.9585

Run: python examples/search/pipeline.py
"""


import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np
from sklearn.datasets import load_digits
from sklearn.decomposition import PCA
from sklearn.linear_model import LogisticRegression as SkLR
from sklearn.metrics import f1_score
from sklearn.model_selection import train_test_split
from sklearn.pipeline import Pipeline
from sklearn.preprocessing import StandardScaler

from skdist_tpu.distribute.search import DistGridSearchCV
from skdist_tpu.models import LogisticRegression


def main():
    X, y = load_digits(return_X_y=True)
    X = X.astype(np.float32)
    X_train, X_test, y_train, y_test = train_test_split(
        X, y, test_size=0.2, random_state=0
    )

    # 1. Pipeline as the base estimator: grid spans steps
    pipe = Pipeline([
        ("scale", StandardScaler()),
        ("pca", PCA(random_state=0)),
        ("clf", SkLR(max_iter=200)),
    ])
    params = {
        "clf__C": [0.1, 1.0, 10.0],
        "pca__n_components": [20, 40],
    }
    model0 = DistGridSearchCV(pipe, params, cv=5, scoring="f1_weighted")
    model0.fit(X_train, y_train)
    print(f"-- Pipeline as base estimator: best CV f1_weighted "
          f"{model0.best_score_:.4f}\n   (best {model0.best_params_})")

    # 2. DistGridSearchCV as the final pipeline step
    model1 = Pipeline([
        ("scale", StandardScaler()),
        ("pca", PCA(n_components=40, random_state=0)),
        ("clf", DistGridSearchCV(
            LogisticRegression(max_iter=100),
            {"C": [0.1, 1.0, 10.0]}, cv=5, scoring="f1_weighted",
        )),
    ])
    model1.fit(X_train, y_train)
    print(f"-- DistGridSearchCV as final pipeline step: best CV "
          f"f1_weighted {model1.steps[-1][1].best_score_:.4f}")

    preds = model0.predict(X_test)
    print(f"-- holdout f1_weighted: "
          f"{f1_score(y_test, preds, average='weighted'):.4f}")


if __name__ == "__main__":
    main()
