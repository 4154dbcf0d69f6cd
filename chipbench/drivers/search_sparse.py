"""
Driver of the grid-search configurations over a scipy CSR matrix: the
same search, fit and units as ``drivers/search.py`` (one
``DistGridSearchCV(...).fit`` through a fresh ``TPUBackend``), with the
inputs from ``datagen_text`` and the answers held against the plain
reference for sparse inputs, ``reference/softmax_lr_sparse.py``.

The reference refits a SAMPLE of the (candidate, fold) pairs where the
configuration's ``compare.sample`` says so: every C at the same number
of folds, which the run's seed draws — all 50 side by side do not fit
beside the reference's dense copy of X (PERF.md has the readings).
"""

import numpy as np

from chipbench import datagen_text
from chipbench.drivers.search import control_answers, fit, units  # noqa: F401


def setup(config, seed, devices):
    lo, hi, num = config["search"]["C_logspace"]
    X, y = datagen_text.make(config["data"], seed)
    return {"config": config, "devices": list(devices), "X": X, "y": y,
            "seed": seed,
            "Cs": [float(c) for c in np.logspace(lo, hi, num)]}


def sample_pairs(state):
    """The ``(candidate, fold)`` pairs the reference refits: all of
    them, or ``compare.sample`` of them — every candidate at as many
    folds as the sample gives each (drawn by the run's seed), so that
    the median of the gaps is taken over the same mix of candidates
    whatever the seed draws (the gaps grow with C: PERF.md), then any
    remainder drawn from the rest without replacement."""
    config = state["config"]
    cv, n_c = int(config["search"]["cv"]), len(state["Cs"])
    every = [(c, f) for c in range(n_c) for f in range(cv)]
    want = config["compare"].get("sample")
    if not want or want >= len(every):
        return every
    rng = np.random.RandomState(int(state["seed"]) % (2 ** 32))
    pairs = [(c, int(f)) for c in range(n_c)
             for f in rng.permutation(cv)[:max(1, want // n_c)]]
    rest = [p for p in every if p not in pairs]
    pairs += [rest[i] for i in rng.permutation(len(rest))[:want - len(pairs)]]
    return pairs


def reference_scores(state, precision="highest", train_stride=1,
                     batches=1):
    """What the plain reference answers for the sampled pairs, as a
    (candidates, folds) array, NaN where no pair was drawn. ``batches``
    refits them in that many batches, one after another, instead of all
    side by side: the same solver summing at another width — a second
    sound answer, for ``control_compare.py``."""
    from chipbench.reference.softmax_lr import stratified_folds
    from chipbench.reference.softmax_lr_sparse import SparseSoftmaxLR

    config = state["config"]
    est = config["estimator"]
    cv = int(config["search"]["cv"])
    ref = SparseSoftmaxLR(state["X"], state["y"], config["data"]["k"],
                          precision)
    folds = stratified_folds(state["y"], cv)
    pairs = sample_pairs(state)
    jobs = [(f, state["Cs"][c]) for c, f in pairs]
    step = -(-len(jobs) // batches)
    scores = [score for at in range(0, len(jobs), step)
              for score in ref.fold_scores(
                  folds, jobs[at:at + step], est["max_iter"], est["tol"],
                  est["history"], train_stride)]
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
