"""
Explicit-config Encoderizer on five feature types (counterpart of the
reference's examples/encoder/multi_type_encoder.py: the point is not
the fitted model but specifying the encoder per column — the complete
option set: string_vectorizer, onehotencoder, multihotencoder,
numeric, dict).

Sample output (CPU backend):
    steps: ['text_col_word_vec', 'categorical_str_col_onehot',
            'categorical_int_col_onehot', 'numeric_col_scaler',
            'dict_col_dict_encoder', 'multilabel_col_multihot']
    best CV score: 1.0000

Run: python examples/encoder/multi_type_encoder.py
"""


import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import pandas as pd

from skdist_tpu.distribute.encoder import Encoderizer
from skdist_tpu.distribute.search import DistGridSearchCV
from skdist_tpu.models import LogisticRegression


def main():
    text = [
        "this is a text encoding example",
        "more random text for the example",
        "even more random text",
    ]
    df = pd.DataFrame({
        "text_col": text * 4,
        "categorical_str_col": ["control", "treatment", "control"] * 4,
        "categorical_int_col": [0, 1, 2] * 4,
        "numeric_col": [5, 22, 69] * 4,
        "dict_col": [{"a": 4}, {"b": 1}, {"c": 3}] * 4,
        "multilabel_col": [["a"], ["a", "b"], ["c"]] * 4,
    })
    y = [0, 1, 1] * 4

    encoder = Encoderizer(config={
        "text_col": "string_vectorizer",
        "categorical_str_col": "onehotencoder",
        "categorical_int_col": "onehotencoder",
        "numeric_col": "numeric",
        "dict_col": "dict",
        "multilabel_col": "multihotencoder",
    })
    X_t = encoder.fit_transform(df)
    print("steps:", encoder.step_names)

    gs = DistGridSearchCV(
        LogisticRegression(max_iter=100), {"C": [0.1, 1.0, 10.0]}, cv=3,
        scoring="accuracy",
    ).fit(X_t, y)
    print(f"best CV score: {gs.best_score_:.4f}")


if __name__ == "__main__":
    main()
