"""Operations an L-BFGS linear-model fit needs, from shapes alone."""


def lbfgs_fit_flops(n_tr, d, k, n_iter):
    """Model FLOPs of one L-BFGS logistic fit: per iteration one
    line-search forward evaluation (``X @ W``: 2 n d k) and one
    value-and-gradient (forward 2 n d k, backward ``X.T @ dL`` 2 n d k),
    6 n d k in all, plus the initial value-and-gradient (4 n d k).
    Backtracking beyond the first trial step and the elementwise
    softmax passes are left out: this is the work a fit REQUIRES,
    whatever kernel runs and however often the line search backtracks,
    so the share of the peak it gives cannot be raised by doing more.
    (Copied from ``bench.lbfgs_fit_flops``.)"""
    return (6.0 * float(n_iter) + 4.0) * float(n_tr) * d * k


def fit_flops(config):
    """FLOPs of ONE (candidate, fold) fit of a search configuration
    that runs to its iteration cap; two classes are one column of
    weights (the binomial loss)."""
    data, cv = config["data"], int(config["search"]["cv"])
    n_tr = data["n"] - data["n"] // cv
    columns = 1 if data["k"] <= 2 else data["k"]
    return lbfgs_fit_flops(n_tr, data["d"], columns,
                           config["estimator"]["max_iter"])

