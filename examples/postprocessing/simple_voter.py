"""
SimpleVoter over heterogeneous pre-fitted models (counterpart of the
reference's examples/postprocessing/simple_voter.py: assemble a voting
classifier from already-fitted estimators — fit lives elsewhere, the
voter is just re-assembly).

Three different model families are fitted independently (each a
distributed fit in its own right), then combined with hard and soft
voting, with weights de-emphasising the weak naive Bayes member.

Sample output (CPU backend):
    -- logreg alone:        accuracy 0.9472
    -- forest alone:        accuracy 0.9639
    -- gaussian NB alone:   accuracy 0.8333
    -- hard voter:          accuracy 0.9583
    -- soft voter (2,2,1):  accuracy 0.9361

Run: python examples/postprocessing/simple_voter.py
"""


import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np
from sklearn.datasets import load_digits
from sklearn.model_selection import train_test_split

from skdist_tpu.distribute.ensemble import DistRandomForestClassifier
from skdist_tpu.models import GaussianNB, LogisticRegression
from skdist_tpu.postprocessing import SimpleVoter


def main():
    X, y = load_digits(return_X_y=True)
    X = (X / 16.0).astype(np.float32)
    X_train, X_test, y_train, y_test = train_test_split(
        X, y, test_size=0.2, random_state=0
    )

    members = [
        ("logreg", LogisticRegression(C=0.1, max_iter=120)),
        ("forest", DistRandomForestClassifier(
            n_estimators=100, max_depth=8, random_state=0)),
        ("gnb", GaussianNB()),
    ]
    for _, est in members:
        est.fit(X_train, y_train)

    def acc(model):
        return float(np.mean(model.predict(X_test) == y_test))

    print(f"-- logreg alone:        accuracy {acc(members[0][1]):.4f}")
    print(f"-- forest alone:        accuracy {acc(members[1][1]):.4f}")
    print(f"-- gaussian NB alone:   accuracy {acc(members[2][1]):.4f}")

    classes = np.unique(y_train)
    hard = SimpleVoter(members, classes, voting="hard")
    print(f"-- hard voter:          accuracy {acc(hard):.4f}")
    soft = SimpleVoter(members, classes, voting="soft", weights=[2, 2, 1])
    print(f"-- soft voter (2,2,1):  accuracy {acc(soft):.4f}")


if __name__ == "__main__":
    main()
