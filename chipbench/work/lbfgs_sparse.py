"""Operations an L-BFGS linear-model fit over a SPARSE matrix needs,
from shapes alone: ``work/lbfgs.py``'s rule with a training fold's
``n * d`` replaced by its stored elements and its intercept column."""

from chipbench.work.lbfgs import lbfgs_fit_flops


def fit_flops(config):
    """FLOPs of ONE (candidate, fold) fit that runs to its iteration
    cap: ``(6 * max_iter + 4) * (nnz_train + n_train) * k``."""
    data, cv = config["data"], int(config["search"]["cv"])
    share = 1.0 - 1.0 / cv
    n_tr = data["n"] - data["n"] // cv
    columns = 1 if data["k"] <= 2 else data["k"]
    return lbfgs_fit_flops(data["nnz"] * share + n_tr, 1, columns,
                           config["estimator"]["max_iter"])
