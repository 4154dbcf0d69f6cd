"""
Seeded inputs, kept here so that a later change to the program's own
scripts cannot change what the benchmark feeds it.

``--seed`` may be a little over 2**31; numpy's ``RandomState`` takes
any value below 2**32, and :func:`rng` folds larger ones into that
range.
"""

import numpy as np


def rng(seed):
    return np.random.RandomState(int(seed) % (2 ** 32))


def dense_unit_rows(seed, n, d, k=2, noise=0.3, block=16384, threads=8):
    """A wide dense binary problem in the shape of the PASCAL
    challenge's ``epsilon`` as LIBSVM ships it normalised: every entry
    nonzero, features of zero mean and equal variance, every row scaled
    to unit L2 norm, two balanced classes that a linear model
    separates up to ``noise`` (about nine rows in ten; linear models
    reach 0.90 on epsilon). Made in blocks of rows, each from a stream
    of its own spawned from the seed, by a few threads (numpy's
    generators release the lock): the same seed gives the same data
    whatever the threads do."""
    from concurrent.futures import ThreadPoolExecutor

    if k != 2:
        raise ValueError("dense_unit_rows makes two classes")
    streams = np.random.SeedSequence(int(seed) % (2 ** 32)).spawn(
        1 + -(-n // block))
    w = np.random.Generator(np.random.SFC64(streams[0])).standard_normal(
        d, dtype=np.float32)
    w *= np.sqrt(d) / np.linalg.norm(w)
    X = np.empty((n, d), dtype=np.float32)
    y = np.empty(n, dtype=np.int64)

    def fill(i):
        bits = np.random.Generator(np.random.SFC64(streams[1 + i]))
        rows = X[i * block:(i + 1) * block]
        bits.standard_normal(out=rows, dtype=np.float32)
        rows /= np.linalg.norm(rows, axis=1, keepdims=True)
        z = rows @ w + noise * bits.standard_normal(len(rows),
                                                    dtype=np.float32)
        y[i * block:(i + 1) * block] = z > 0

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(len(streams) - 1)))
    return X, y


GENERATORS = {"dense_unit_rows": dense_unit_rows}


def make(data, seed):
    """The inputs a configuration's ``data`` group describes: its
    ``generator`` called with the run's seed and the group's other
    keys — or with the group's own ``seed``, where it pins one.

    A configuration pins its data where the run's seed would change
    the WORK. A grid search is run on one fixed data set (epsilon is
    one), and its cost is not a function of shapes
    alone: the backtracking line search evaluates the loss more or
    less often on other data, and a vmapped round waits for its
    slowest lane. Measured on the chip (PR 25, on the 4,096-column
    text proxy of this PR's first sessions): two runs on the same
    data differ by 0.1 % (one pair in six by 1 %), runs on one corpus
    with its columns and class labels permuted by the seed by up to
    5.6 % (quartiles 2.6 % of the median apart), on corpora redrawn
    from the seed by +-4 %. Nothing about the data can be reordered
    without changing the order of some float32 sum, and with it a
    line-search decision somewhere in the fits' iterations."""
    kwargs = {k: v for k, v in data.items() if k not in ("generator", "seed")}
    return GENERATORS[data["generator"]](data.get("seed", seed), **kwargs)
