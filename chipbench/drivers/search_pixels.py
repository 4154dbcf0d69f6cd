"""
Driver of the grid-search configurations over a dense multiclass
matrix that fills most of the chip: the same search, fit and units as
``drivers/search.py`` (one ``DistGridSearchCV(...).fit`` through a
fresh ``TPUBackend``), with the inputs from ``datagen_pixels`` and the
answers held against the plain reference that walks X in blocks,
``reference/softmax_lr_blocked.py``.

The reference refits every (candidate, fold) pair, in as many batches
one after another as the configuration's ``compare.batches`` says (the
limits were read against that reference: a batch's width is part of how
its float32 sums round, PERF.md section 2) — or, where
``compare.sample`` is set (the tests' small size), a sample drawn as
``drivers/search_sparse.sample_pairs`` draws it.
"""

import numpy as np

from chipbench import datagen_pixels
from chipbench.drivers.search import control_answers, fit, units  # noqa: F401
from chipbench.drivers.search_sparse import sample_pairs  # noqa: F401


def setup(config, seed, devices):
    lo, hi, num = config["search"]["C_logspace"]
    X, y = datagen_pixels.make(config["data"], seed)
    return {"config": config, "devices": list(devices), "X": X, "y": y,
            "seed": seed,
            "Cs": [float(c) for c in np.logspace(lo, hi, num)]}


def reference_scores(state, precision="highest", train_stride=1,
                     batches=None):
    """What the plain reference answers for the compared pairs, as a
    (candidates, folds) array, NaN where no pair was drawn. ``batches``
    (default: the configuration's ``compare.batches``, else 1) refits
    them in that many batches, one after another: another count is the
    same solver summing at another width — a second sound answer, for
    ``control_compare.py``."""
    from chipbench.reference.softmax_lr import stratified_folds
    from chipbench.reference.softmax_lr_blocked import (
        BlockedSoftmaxLR, sampled_fold_scores,
    )

    config = state["config"]
    cv = int(config["search"]["cv"])
    ref = BlockedSoftmaxLR(
        state["X"], state["y"], config["data"]["k"], precision,
        **config["compare"].get("reference", {}))
    pairs = sample_pairs(state)
    scores = sampled_fold_scores(
        ref, stratified_folds(state["y"], cv), pairs, state["Cs"],
        config["estimator"], train_stride,
        batches or config["compare"].get("batches", 1))
    out = np.full((len(state["Cs"]), cv), np.nan)
    for (c, f), score in zip(pairs, scores):
        out[c, f] = score
    return out


def compare(state, answers):
    """Every sampled answer of every window fit against the plain
    reference's fit of the same fold at the same C: the median and the
    widest gap in fold log-loss, the worst fit of the window counting
    (``drivers/search.compare``'s arithmetic over the drawn pairs)."""
    limits = state["config"]["compare"]["limits"]
    want = reference_scores(state)
    drawn = np.isfinite(want)
    med = worst = 0.0
    for scores in answers:
        gap = np.abs(scores[drawn] - want[drawn])
        gap = np.where(np.isfinite(gap), gap, np.inf)
        med, worst = max(med, float(np.median(gap))), max(
            worst, float(np.max(gap)))
    return [
        {"name": "ll_gap_median", "value": med,
         "limit": limits["ll_gap_median"]},
        {"name": "ll_gap_max", "value": worst,
         "limit": limits["ll_gap_max"]},
    ]
