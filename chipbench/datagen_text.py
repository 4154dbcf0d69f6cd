"""
Seeded bag-of-words inputs in the shape of a vectorised text corpus:
a scipy CSR matrix whose row lengths are heavy-tailed, as documents'
are. Beside ``datagen.py`` (which may not change): a configuration
whose driver is ``search_sparse`` names a generator of THIS module.
"""

import numpy as np

from chipbench import datagen


def bag_of_words(seed, n, d, k, nnz, len_sigma=0.861, len_cap=5000,
                 rank_shift=10, topic_terms=1500, topic_share=0.35,
                 label_noise=0.1, oversample=3.0):
    """``(X, y)``: ``X`` an ``(n, d)`` float32 CSR of TF-IDF-like
    values with rows of unit L2 norm and about ``nnz`` stored elements,
    ``y`` one of ``k`` roughly balanced classes.

    Row lengths (distinct terms a document) are log-normal with
    ``len_sigma`` (0.861 with a mean of 158 puts the 95th percentile at
    450 and the longest of 11,314 rows near 3,000), capped at
    ``len_cap`` and rescaled so that they sum to ``nnz``. A document
    draws ``oversample`` tokens for every distinct term it wants and
    keeps the first it needs: each token comes with probability
    ``topic_share`` from its class's topic — a Zipf law over
    ``topic_terms`` terms of the class's own — and else from the
    background, a Zipf law ``1 / (rank + rank_shift)`` over all ``d``
    terms. A value is ``(1 + log count) * idf``. ``label_noise`` of the
    labels are redrawn uniformly, which bounds what any model can
    score: a linear model separates the rest almost wholly, so it
    reaches about ``1 - label_noise * (k - 1) / k``."""
    import scipy.sparse as sp

    rng = datagen.rng(seed)
    y_true = rng.permutation(n) % k
    lens = rng.lognormal(0.0, len_sigma, n)
    lens = np.minimum(lens * (nnz / lens.sum()), len_cap)
    lens = np.maximum(1, np.round(lens * (nnz / lens.sum()))).astype(np.int64)

    background = 1.0 / (np.arange(d) + rank_shift)
    background_cdf = np.cumsum(background / background.sum())
    topic = 1.0 / (np.arange(topic_terms) + rank_shift)
    topic_cdf = np.cumsum(topic / topic.sum())
    # each class's topic: terms of its own from the middle of the law
    topics = np.stack([rng.choice(np.arange(50, d // 4), topic_terms,
                                  replace=False) for _ in range(k)])

    draws = np.ceil(lens * oversample).astype(np.int64)
    doc = np.repeat(np.arange(n), draws)
    u = rng.random_sample(doc.size)
    term = np.minimum(np.searchsorted(background_cdf, u), d - 1)
    from_topic = rng.random_sample(doc.size) < topic_share
    pick = np.minimum(np.searchsorted(topic_cdf, u[from_topic]),
                      topic_terms - 1)
    term[from_topic] = topics[y_true[doc[from_topic]], pick]

    # distinct (document, term) pairs with their counts and the place
    # of each pair's first token; a document keeps the first it wants
    key = doc * np.int64(d) + term
    uniq, first, count = np.unique(key, return_index=True,
                                   return_counts=True)
    udoc = uniq // d
    order = np.lexsort((first, udoc))
    udoc, uterm, count = udoc[order], (uniq % d)[order], count[order]
    start = np.searchsorted(udoc, np.arange(n))
    keep = np.arange(udoc.size) - start[udoc] < lens[udoc]
    udoc, uterm, count = udoc[keep], uterm[keep], count[keep]

    df = np.bincount(uterm, minlength=d)
    value = (1.0 + np.log(count)) * (np.log(n / (1.0 + df[uterm])) + 1.0)
    X = sp.csr_matrix((value, (udoc, uterm)), shape=(n, d), dtype=np.float64)
    X.sort_indices()
    norms = np.sqrt(np.asarray(X.multiply(X).sum(axis=1)).ravel())
    X = sp.diags(1.0 / np.maximum(norms, 1e-12)).dot(X).astype(np.float32)
    X = X.tocsr()
    X.sort_indices()

    noisy = rng.random_sample(n) < label_noise
    y = np.where(noisy, rng.randint(0, k, n), y_true).astype(np.int64)
    return X, y


GENERATORS = {"bag_of_words": bag_of_words}


def make(data, seed):
    """``datagen.make`` over this module's generators: the group's own
    ``seed`` where it pins one (``datagen.make`` says why), else the
    run's."""
    kwargs = {key: v for key, v in data.items()
              if key not in ("generator", "seed")}
    return GENERATORS[data["generator"]](data.get("seed", seed), **kwargs)
