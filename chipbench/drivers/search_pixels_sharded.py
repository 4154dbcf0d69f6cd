"""
Driver of the grid-search configurations over a dense multiclass
matrix that NO single chip holds: the inputs, the units, the drawn
pairs and the comparison of ``drivers/search_pixels.py``, with a fit of
its own — one ``DistGridSearchCV(...).fit`` through a fresh
``TPUBackend`` over ALL the cell's chips, ``tasks`` 1 x ``data``
<chips>: the matrix row-sharded, every lane on every chip, the refit on
the same mesh — and the answers held against the plain reference that
keeps a part of the rows on each chip and adds the parts' sums on the
host, ``reference/softmax_lr_rowsharded.py`` (no mesh, no collective).

``setup`` first asks the program whether it can run the configuration
at all (its round plans count a device's share of row-sharded data:
``IterativePlan.data_shards``) and ends the run at once, non-zero, where
it cannot — a program without it sizes its rounds against the whole
25.4 GB and refits on one chip, and takes minutes and every row of the
generator to find that out.
"""

import numpy as np

from chipbench.drivers import search_pixels
from chipbench.drivers.search_pixels import sample_pairs, units  # noqa: F401


def setup(config, seed, devices):
    from skdist_tpu.parallel.backend import IterativePlan

    if "data_shards" not in getattr(IterativePlan, "__slots__", ()):
        raise SystemExit(
            "chipbench: this program cannot run a row-sharded "
            "configuration: its round plans book no `data_shards` (round "
            "sizing counts the whole matrix against one chip, and the "
            "refit places it whole on one); no result")
    return search_pixels.setup(config, seed, devices)


def fit(state):
    """``(units failed, round stats, answer)`` of one search on the
    mesh of all the state's devices, the rows of X shared between
    them; the answer is the (candidates, folds) array of test scores
    the search assembled."""
    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.parallel import TPUBackend

    config = state["config"]
    search = config["search"]
    backend = TPUBackend(devices=state["devices"],
                         data_axis_size=len(state["devices"]))
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
    operands in the solver's matmuls (``matmul_dtype``), one fit on the
    same mesh."""
    config = dict(state["config"])
    config["estimator"] = dict(config["estimator"], matmul_dtype="bfloat16")
    return [fit(dict(state, config=config))[2]]


def reference_scores(state, precision="highest", train_stride=1,
                     batches=None):
    """What the plain reference answers for the compared pairs, as a
    (candidates, folds) array, NaN where no pair was drawn: the rows in
    as many parts as the state has devices, a part a device.
    ``batches`` (default: the configuration's ``compare.batches``, else
    1) refits the pairs in that many batches, one after another:
    another count is the same solver summing at another width — a
    second sound answer, for ``control_compare.py``."""
    from chipbench.reference.softmax_lr import stratified_folds
    from chipbench.reference.softmax_lr_rowsharded import (
        RowShardedSoftmaxLR, sampled_fold_scores,
    )

    config = state["config"]
    cv = int(config["search"]["cv"])
    ref = RowShardedSoftmaxLR(
        state["X"], state["y"], config["data"]["k"], state["devices"],
        precision, **config["compare"].get("reference", {}))
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
    """``drivers/search_pixels.compare`` against THIS module's
    reference: every drawn answer of every window fit against the plain
    reference's fit of the same fold at the same C, the median and the
    widest gap in fold log-loss, the worst fit of the window
    counting."""
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
