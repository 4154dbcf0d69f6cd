"""
Naive Bayes kernels: Gaussian and Multinomial.

Closed-form fits (per-class weighted moments / counts — a couple of
matmuls), which makes them the cheapest members of the batched-fit
contract: a CV sweep is one vmapped program of segment reductions.
The reference exercised sklearn's GaussianNB through
DistMultiModelSearch (reference test_search.py multimodel test) and
text models through the Encoderizer pipelines.

Numerical notes: Gaussian moments are computed on globally-centred
data (bounding magnitudes by the inter-class spread) so the
E[x²]−mean² form doesn't catastrophically cancel in float32; the
Gaussian decision is expressed as three matmuls, never materialising
an (n, k, d) intermediate.
"""

import numpy as np
import jax
import jax.numpy as jnp

from .linear import (
    LogisticRegression,
    _LinearClassifierBase,
)

__all__ = ["GaussianNB", "MultinomialNB"]


class GaussianNB(_LinearClassifierBase):
    """Gaussian naive Bayes with weighted per-class moments.

    ``var_smoothing`` (sklearn semantics: added variance =
    var_smoothing · max feature variance) is a batchable hyper.
    """

    _hyper_names = ("var_smoothing",)
    _static_names = ()

    def __init__(self, var_smoothing=1e-9):
        self.var_smoothing = var_smoothing

    @classmethod
    def _build_fit_kernel(cls, meta, static):
        k = meta["n_classes"]

        def kernel(X, y_idx, sw, hyper, aux=None):
            vs = hyper["var_smoothing"]
            tot_w = jnp.maximum(jnp.sum(sw), 1e-12)
            gmean = jnp.sum(sw[:, None] * X, axis=0) / tot_w
            Xc = X - gmean  # centred: bounds moment magnitudes
            oh = jax.nn.one_hot(y_idx, k, dtype=X.dtype) * sw[:, None]
            cw = jnp.sum(oh, axis=0)  # (k,)
            means_c = (oh.T @ Xc) / jnp.maximum(cw[:, None], 1e-12)
            sq = oh.T @ (Xc * Xc)
            var = sq / jnp.maximum(cw[:, None], 1e-12) - means_c**2
            gvar = jnp.sum(sw[:, None] * Xc * Xc, axis=0) / tot_w
            var = jnp.maximum(var, 0.0) + vs * jnp.max(gvar)
            priors = cw / tot_w
            return {
                "gmean": gmean,
                "means_c": means_c,
                "var": var,
                "log_prior": jnp.log(jnp.maximum(priors, 1e-12)),
            }

        return kernel

    @classmethod
    def _build_decision_kernel(cls, meta, static):
        @jax.jit
        def decision(params, X):
            m, var = params["means_c"], params["var"]
            Xc = X - params["gmean"]
            # -(1/2)[Σ log 2πσ² + Σ (x-m)²/σ²] as matmuls, no (n,k,d)
            const = -0.5 * (
                jnp.sum(jnp.log(2.0 * jnp.pi * var), axis=1)
                + jnp.sum(m * m / var, axis=1)
            )  # (k,)
            lin = Xc @ (m / var).T  # (n, k)
            quad = -0.5 * ((Xc * Xc) @ (1.0 / var).T)  # (n, k)
            return quad + lin + const[None, :] + params["log_prior"][None, :]

        return decision

    @classmethod
    def _build_proba_kernel(cls, meta, static):
        decision = cls._build_decision_kernel(meta, static)

        @jax.jit
        def proba(params, X):
            return jax.nn.softmax(decision(params, X), axis=1)

        return proba

    predict_proba = LogisticRegression.predict_proba
    predict_log_proba = LogisticRegression.predict_log_proba


class MultinomialNB(_LinearClassifierBase):
    """Multinomial naive Bayes (count features, e.g. hashed text).

    ``alpha`` (Lidstone smoothing, clamped to ≥1e-10 like sklearn) is a
    batchable hyper. The decision is linear in X, so ``coef_`` /
    ``intercept_`` expose the per-class feature log-probabilities and
    log-priors.
    """

    _hyper_names = ("alpha",)
    _static_names = ()

    def __init__(self, alpha=1.0):
        self.alpha = alpha

    @classmethod
    def _build_fit_kernel(cls, meta, static):
        k = meta["n_classes"]

        def kernel(X, y_idx, sw, hyper, aux=None):
            alpha = jnp.maximum(hyper["alpha"], 1e-10)
            oh = jax.nn.one_hot(y_idx, k, dtype=X.dtype) * sw[:, None]
            counts = oh.T @ X  # (k, d) per-class feature totals
            smoothed = counts + alpha
            log_p = jnp.log(smoothed) - jnp.log(
                jnp.sum(smoothed, axis=1, keepdims=True)
            )
            cw = jnp.sum(oh, axis=0)
            log_prior = jnp.log(
                jnp.maximum(cw / jnp.maximum(jnp.sum(sw), 1e-12), 1e-12)
            )
            # linear form: decision = X @ log_p.T + log_prior
            W = jnp.concatenate([log_p.T, log_prior[None, :]], axis=0)
            return {"W": W}

        return kernel

    def _prep_fit_data(self, X, y, sample_weight=None):
        # (a placed X — a search's refit, ``_fit_placed`` — answers
        # where it lies and is not brought back to the host)
        if (X if hasattr(X, "sharding") else np.asarray(X)).min() < 0:
            raise ValueError(
                "Negative values in data passed to MultinomialNB "
                "(input X must be non-negative counts)"
            )
        return super()._prep_fit_data(X, y, sample_weight)

    @classmethod
    def _build_decision_kernel(cls, meta, static):
        d = meta["n_features"]

        @jax.jit
        def decision(params, X):
            W = params["W"]
            return X @ W[:d] + W[d]

        return decision

    @classmethod
    def _build_proba_kernel(cls, meta, static):
        decision = cls._build_decision_kernel(meta, static)

        @jax.jit
        def proba(params, X):
            return jax.nn.softmax(decision(params, X), axis=1)

        return proba

    predict_proba = LogisticRegression.predict_proba
    predict_log_proba = LogisticRegression.predict_log_proba
