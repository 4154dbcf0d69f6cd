"""
Plain reference for the logistic-regression grid search: what one
(candidate, fold) fit of the search has to answer.

The answer of a fit is the test fold's negative mean log-loss of the
L2-regularised logistic regression (sklearn's objective:
``sum_i logloss_i + ||W||^2 / (2 C)``, the intercept unpenalised;
multinomial over ``k`` columns, or for two classes binomial over one)
trained on the other folds by the configuration's solver: L-BFGS for
at most ``max_iter`` iterations from zero. Most fits of a grid stop at
the iteration cap and not at the optimum, so the answer is a point on
the solver's path (a reference that solves to the optimum read 1e-5 to
1e-4 from a sound fit and no more from a bfloat16 one: PERF.md). The
reference therefore writes the solver out plainly
(:meth:`SoftmaxLR.fit_many`) and follows the same path in its own
arithmetic: vectors in float64 on the host, loss and gradient in
float32 with the matmul at ``highest`` precision on the default device,
over ALL rows with a 0/1 row mask (one compiled program whatever the
fold). Several fits advance in lock step, each by its own decisions,
so that one read of the data serves them all: a fit alone takes as long
as a dozen together. It imports nothing of
``skdist_tpu`` and takes nothing the program made.

``precision="high"`` computes the matmul in three bfloat16 passes, the
step below ``highest``. It is for the control: the reference put in the
program's place one precision down (on a chip only: a CPU computes
``high`` exactly).
"""

import numpy as np


def stratified_folds(y, n_splits):
    """``[(train_idx, test_idx)]`` as sklearn's unshuffled
    StratifiedKFold cuts them — what ``cv=<int>`` means for a
    classifier in sklearn and in the program."""
    from sklearn.model_selection import StratifiedKFold

    placeholder = np.zeros((len(y), 1), np.float32)
    return list(StratifiedKFold(n_splits=n_splits).split(placeholder, y))


class SoftmaxLR:
    """The data placed once; :meth:`fit_many` some folds at some C."""

    def __init__(self, X, y, n_classes, precision="highest"):
        import jax
        import jax.numpy as jnp

        if precision not in ("highest", "high"):
            raise ValueError(f"unknown precision {precision!r}")
        self.n, self.d = X.shape
        # two classes: one column, the binomial loss on the second class
        self.k = 1 if int(n_classes) <= 2 else int(n_classes)
        self._X = jnp.asarray(X, jnp.float32)
        self._onehot = (
            jnp.asarray(np.asarray(y) == 1, jnp.float32)[:, None]
            if self.k == 1 else
            jax.nn.one_hot(jnp.asarray(y), self.k, dtype=jnp.float32))
        d, k = self.d, self.k

        def row_loss(wflat, X, onehot):
            Wb = wflat.reshape(d + 1, k)
            z = jnp.matmul(X, Wb[:d], precision=precision) + Wb[d]
            if k == 1:
                return (jax.nn.softplus(z) - onehot * z)[:, 0]
            return jax.nn.logsumexp(z, axis=1) - jnp.sum(onehot * z, axis=1)

        def objective(wflat, X, onehot, mask, inv_c):
            W = wflat.reshape(d + 1, k)[:d]
            return (jnp.sum(mask * row_loss(wflat, X, onehot))
                    + 0.5 * inv_c * jnp.sum(W * W))

        # one fit's objective, mapped over the fits of a batch (their
        # weights, row masks and 1/C); the data are shared ARGUMENTS of
        # the compiled programs, not constants in them
        over_fits = (0, None, None, 0, 0)
        values = jax.jit(jax.vmap(objective, over_fits))
        values_and_grads = jax.jit(jax.vmap(jax.value_and_grad(objective),
                                            over_fits))
        rows = jax.jit(row_loss)
        self._values = lambda W, m, c: values(W, self._X, self._onehot, m, c)
        self._values_and_grads = lambda W, m, c: values_and_grads(
            W, self._X, self._onehot, m, c)
        self._row_loss = lambda w: rows(w, self._X, self._onehot)

    @staticmethod
    def _direction(g, pairs, n_stored, eps):
        """The two-loop recursion's quasi-Newton direction, steepest
        descent where it does not descend; a raw gradient direction
        (the first, or that fallback) at unit length."""
        q = g.copy()
        alphas = []
        for s, yv, rho in reversed(pairs):
            a = rho * np.dot(s, q)
            q -= a * yv
            alphas.append(a)
        if pairs:
            s, yv, _ = pairs[-1]
            q *= np.dot(s, yv) / (np.dot(yv, yv) + eps)
        for (s, yv, rho), a in zip(pairs, reversed(alphas)):
            q += s * (a - rho * np.dot(yv, q))
        direction = -q
        descent = np.dot(g, direction) < 0
        if not descent:
            direction = -g
        if not descent or n_stored == 0:
            direction = direction / (np.linalg.norm(direction) + eps)
        return direction

    def fit_many(self, jobs, max_iter, tol, history=10, max_ls=20):
        """The configuration's solver, written out plainly, for every
        ``(train_idx, C)`` of ``jobs`` side by side: L-BFGS from zero
        (two-loop recursion over the last ``history`` curvature pairs,
        initial scaling ``s.y / y.y``), Armijo backtracking from step 1
        by halving (``c1 = 1e-4``, at most ``max_ls`` halvings), the
        first direction and any non-descent fallback normalised to unit
        length, a pair stored only when ``s.y > 1e-10``; a fit stops
        when ``max|grad| <= tol``, when its line search finds no
        decrease, or after ``max_iter`` iterations, and then waits
        unchanged for the others. Vector arithmetic in float64 on the
        host, fit by fit; every evaluation of loss and gradient is one
        float32 device call for the whole batch.

        Returns ``[(weights, iterations)]``, the weights flat
        ``(d + 1) * k`` with the intercept in the last row."""
        import jax.numpy as jnp

        masks = np.zeros((len(jobs), self.n), np.float32)
        for j, (train_idx, _) in enumerate(jobs):
            masks[j, train_idx] = 1.0
        masks = jnp.asarray(masks)
        inv_c = jnp.asarray([1.0 / C for _, C in jobs], jnp.float32)
        eps = 1e-12

        def values(W):
            return np.asarray(self._values(jnp.asarray(W, jnp.float32),
                                           masks, inv_c), np.float64)

        def values_and_grads(W):
            f, G = self._values_and_grads(jnp.asarray(W, jnp.float32),
                                          masks, inv_c)
            return np.asarray(f, np.float64), np.asarray(G, np.float64)

        W = np.zeros((len(jobs), (self.d + 1) * self.k))
        f, G = values_and_grads(W)
        pairs = [[] for _ in jobs]  # (s, y, rho) of each fit, oldest first
        n_stored = np.zeros(len(jobs), int)
        it = np.zeros(len(jobs), int)
        live = (np.max(np.abs(G), axis=1) > tol) & (max_iter > 0)
        while live.any():
            D = np.zeros_like(W)  # a stopped fit's step is nought
            for j in np.flatnonzero(live):
                D[j] = self._direction(G[j], pairs[j], n_stored[j], eps)
            gd = np.sum(G * D, axis=1)
            t = np.ones(len(jobs))
            n_ls = np.zeros(len(jobs), int)
            f_new = values(W + D)
            while True:
                back = (live & (f_new > f + 1e-4 * t * gd)
                        & (n_ls < max_ls))
                if not back.any():
                    break
                t[back] *= 0.5
                n_ls[back] += 1
                f_new = np.where(back, values(W + t[:, None] * D), f_new)
            ok = f_new <= f + 1e-4 * t * gd
            W_new = W + t[:, None] * D
            f_new, G_new = values_and_grads(W_new)
            for j in np.flatnonzero(live):
                s, yv = W_new[j] - W[j], G_new[j] - G[j]
                sy = np.dot(s, yv)
                if sy > 1e-10:
                    pairs[j] = (pairs[j] + [(s, yv, 1.0 / (sy + eps))]
                                )[-history:]
                    n_stored[j] += 1
            W[live], f[live], G[live] = W_new[live], f_new[live], G_new[live]
            it[live] += 1
            live &= ~((np.max(np.abs(G), axis=1) <= tol) | ~ok
                      | (it >= max_iter))
        return [(W[j], int(it[j])) for j in range(len(jobs))]

    def neg_log_loss(self, wflat, test_idx):
        """``-mean(logloss)`` over the test rows, summed in float64."""
        import jax.numpy as jnp

        rows = np.asarray(self._row_loss(jnp.asarray(wflat, jnp.float32)),
                          np.float64)
        return -float(np.mean(rows[test_idx]))

    def fold_scores(self, folds, pairs, max_iter, tol, history=10,
                    train_stride=1):
        """The answers of the ``(fold, C)`` fits of ``pairs``, fitted
        side by side. ``train_stride=2`` trains each on every second
        row of its fold: the fault "half the rows left out", for the
        limits' readings."""
        fits = self.fit_many(
            [(folds[fold][0][::train_stride], C) for fold, C in pairs],
            max_iter, tol, history)
        return [self.neg_log_loss(w, folds[fold][1])
                for (w, _), (fold, _) in zip(fits, pairs)]
