"""
Driver of the grid-search configurations: one fit is one
``DistGridSearchCV(...).fit`` through a fresh ``TPUBackend``, as a
user's job makes it; its units are the (candidate, fold) fits.
"""

import numpy as np

from chipbench import datagen


def setup(config, seed, devices):
    data, search = config["data"], config["search"]
    X, y = datagen.make(data, seed)
    lo, hi, num = search["C_logspace"]
    return {"config": config, "devices": list(devices), "X": X, "y": y,
            "Cs": [float(c) for c in np.logspace(lo, hi, num)]}


def units(state):
    return len(state["Cs"]) * int(state["config"]["search"]["cv"])


def fit(state):
    """``(units failed, round stats, answer)``: the answer is the
    (candidates, folds) array of test scores the search assembled."""
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.parallel import TPUBackend

    config = state["config"]
    search = config["search"]
    backend = TPUBackend(devices=state["devices"])
    gs = DistGridSearchCV(
        LogisticRegression(**config["estimator"]), {"C": state["Cs"]},
        backend=backend, cv=search["cv"], scoring=search["scoring"],
        error_score="raise",
    ).fit(state["X"], state["y"])
    scores = np.array(
        [[gs.cv_results_[f"split{s}_test_score"][i]
          for s in range(search["cv"])] for i in range(len(state["Cs"]))],
        dtype=np.float64)
    failed = int(np.sum(~np.isfinite(scores)))
    return failed, dict(backend.last_round_stats or {}), scores


def control_answers(state):
    """The program's own lower-precision path, switched on: bfloat16
    operands in the solver's matmuls (``matmul_dtype``), one fit."""
    config = dict(state["config"])
    config["estimator"] = dict(config["estimator"], matmul_dtype="bfloat16")
    return [fit(dict(state, config=config))[2]]


def reference_scores(state, precision="highest", train_stride=1):
    """What the plain reference answers for every (candidate, fold),
    as the (candidates, folds) array of the program's answers; the
    fits advance side by side in one batch."""
    from chipbench.reference.softmax_lr import SoftmaxLR, stratified_folds

    config = state["config"]
    est = config["estimator"]
    ref = SoftmaxLR(state["X"], state["y"], config["data"]["k"], precision)
    cv = int(config["search"]["cv"])
    folds = stratified_folds(state["y"], cv)
    scores = ref.fold_scores(
        folds, [(f, C) for C in state["Cs"] for f in range(cv)],
        est["max_iter"], est["tol"], est["history"], train_stride)
    return np.array(scores).reshape(len(state["Cs"]), cv)


def compare(state, answers):
    """Every answer of every window fit against the plain reference's
    fit of the same fold at the same C: the median and the widest gap
    in fold log-loss, the worst fit of the window counting."""
    limits = state["config"]["compare"]["limits"]
    want = reference_scores(state)
    med = worst = 0.0
    for scores in answers:
        gap = np.abs(scores - want)
        gap = np.where(np.isfinite(gap), gap, np.inf)
        med, worst = max(med, float(np.median(gap))), max(
            worst, float(np.max(gap)))
    return [
        {"name": "ll_gap_median", "value": med,
         "limit": limits["ll_gap_median"]},
        {"name": "ll_gap_max", "value": worst,
         "limit": limits["ll_gap_max"]},
    ]
