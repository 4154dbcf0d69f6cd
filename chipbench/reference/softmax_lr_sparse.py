"""
Plain reference for the logistic-regression grid search over a SPARSE
matrix: what one (candidate, fold) fit of the search has to answer —
``softmax_lr.py``'s objective and solver (that file says why the answer
is a point on the solver's path and not the optimum), on a
representation of its own and at a width where a fit's vectors are
``(d + 1) * k`` = 2.6 M floats.

The matrix is DENSIFIED on the device, once (11,314 x 130,107 float32 =
5.9 GB, after the program's state is dropped): every loss and gradient
is then one dense float32 matmul at ``highest`` precision for all the
fits of a batch side by side, over ALL rows with a 0/1 row mask — no
packed rows, gathers or buckets, nothing the program's sparse plane
does. A trial step of the line search is evaluated from the logits of
the point and of the direction (they are linear in the weights), so an
iteration costs three matmuls however often it halves. The solver's
vectors stay on the device in float32 (a batch's history alone is
2.6 GB; on the host, in float64, the two-loop recursions of a dozen
fits would take minutes), and every decision — Armijo test, curvature
test, stop — is taken on the host, fit by fit, from scalars. It imports
nothing of ``skdist_tpu`` and takes nothing the program made.

``precision="high"`` computes the matmuls in three bfloat16 passes, the
step below ``highest`` (on a chip only: a CPU computes ``high``
exactly).
"""

import numpy as np


class SparseSoftmaxLR:
    """The data placed once; :meth:`fit_many` some folds at some C."""

    def __init__(self, X, y, n_classes, precision="highest"):
        import jax
        import jax.numpy as jnp

        if precision not in ("highest", "high"):
            raise ValueError(f"unknown precision {precision!r}")
        X = X.tocoo()
        self.n, self.d = X.shape
        self.k = 1 if int(n_classes) <= 2 else int(n_classes)
        n, d, k = self.n, self.d, self.k
        self._X = jax.jit(
            lambda r, c, v: jnp.zeros((n, d), jnp.float32).at[r, c].add(v)
        )(jnp.asarray(X.row), jnp.asarray(X.col),
          jnp.asarray(X.data, jnp.float32))
        self._onehot = (
            jnp.asarray(np.asarray(y) == 1, jnp.float32)[:, None]
            if k == 1 else
            jax.nn.one_hot(jnp.asarray(y), k, dtype=jnp.float32))

        def logits(W, X):
            """``(fits, n, k)`` logits of ``W (fits, (d + 1) * k)``: one
            matmul for all the fits. Linear in ``W``, bias and all."""
            fits = W.shape[0]
            Wb = W.reshape(fits, d + 1, k)
            wide = jnp.moveaxis(Wb[:, :d], 0, 1).reshape(d, fits * k)
            z = jnp.matmul(X, wide, precision=precision).reshape(n, fits, k)
            return jnp.moveaxis(z, 1, 0) + Wb[:, d][:, None, :]

        def row_loss(z, onehot):
            if k == 1:
                return (jax.nn.softplus(z) - onehot * z)[..., 0]
            return jax.nn.logsumexp(z, axis=2) - jnp.sum(onehot * z, axis=2)

        def values_at(z, W, onehot, mask, inv_c):
            """Each fit's objective at weights ``W`` whose logits are
            ``z``."""
            Wb = W.reshape(W.shape[0], d + 1, k)[:, :d]
            return (jnp.sum(mask * row_loss(z, onehot), axis=1)
                    + 0.5 * inv_c * jnp.sum(Wb * Wb, axis=(1, 2)))

        def total(W, X, onehot, mask, inv_c):
            z = logits(W, X)
            values = values_at(z, W, onehot, mask, inv_c)
            return jnp.sum(values), (values, z)

        def along(t, W, D, z, dz, onehot, mask, inv_c):
            """The objectives at ``W + t * D`` from the logits of ``W``
            and of ``D``: a trial step of the line search costs no
            product (the logits are linear in the weights)."""
            return values_at(z + t[:, None, None] * dz, W + t[:, None] * D,
                             onehot, mask, inv_c)

        self._logits = jax.jit(logits)
        self._along = jax.jit(along)
        # the fits' objectives share no weight, so the gradient of
        # their sum is each fit's own gradient
        self._values_and_grads = jax.jit(
            jax.grad(total, has_aux=True))
        self._row_loss = jax.jit(
            lambda W, X, onehot: row_loss(logits(W, X), onehot))

    @staticmethod
    def _direction(g, S, Y, rho, stored, eps):
        """The two-loop recursion's quasi-Newton direction for ONE fit
        (``S``, ``Y``: its last pairs, oldest first), steepest descent
        where it does not descend; a raw gradient direction (the first,
        or that fallback) at unit length. float32 on the device."""
        import jax.numpy as jnp

        q = g
        alphas = []
        for j in reversed(range(len(S))):
            a = rho[j] * jnp.dot(S[j], q)
            q = q - a * Y[j]
            alphas.append(a)
        if S:
            q = q * (jnp.dot(S[-1], Y[-1]) / (jnp.dot(Y[-1], Y[-1]) + eps))
        for j, a in zip(range(len(S)), reversed(alphas)):
            q = q + S[j] * (a - rho[j] * jnp.dot(Y[j], q))
        direction = -q
        descent = bool(jnp.dot(g, direction) < 0)
        if not descent:
            direction = -g
        if not descent or stored == 0:
            direction = direction / (jnp.linalg.norm(direction) + eps)
        return direction

    def fit_many(self, jobs, max_iter, tol, history=10, max_ls=20):
        """``softmax_lr.SoftmaxLR.fit_many``'s solver for every
        ``(train_idx, C)`` of ``jobs`` side by side — L-BFGS from zero,
        Armijo backtracking from step 1 by halving (``c1 = 1e-4``, at
        most ``max_ls`` halvings), the first direction and any
        non-descent fallback at unit length, a pair stored only when
        ``s.y > 1e-10``; a fit stops when ``max|grad| <= tol``, when
        its line search finds no decrease, or after ``max_iter``
        iterations, and then waits unchanged for the others — with the
        vectors on the device. Returns ``[(weights, iterations)]``, the
        weights flat ``(d + 1) * k`` (a device array) with the
        intercept in the last row."""
        import jax.numpy as jnp

        masks = np.zeros((len(jobs), self.n), np.float32)
        for j, (train_idx, _) in enumerate(jobs):
            masks[j, train_idx] = 1.0
        masks = jnp.asarray(masks)
        inv_c = jnp.asarray([1.0 / C for _, C in jobs], jnp.float32)
        eps = 1e-12
        data = (self._X, self._onehot, masks, inv_c)

        def values_and_grads(W):
            G, (f, z) = self._values_and_grads(W, *data)
            return np.asarray(f, np.float64), G, z

        fits = len(jobs)
        W = jnp.zeros((fits, (self.d + 1) * self.k), jnp.float32)
        f, G, z = values_and_grads(W)
        pairs = [([], [], []) for _ in jobs]  # S, Y, rho: oldest first
        stored = np.zeros(fits, int)
        it = np.zeros(fits, int)
        live = (np.asarray(jnp.max(jnp.abs(G), axis=1)) > tol) & (
            max_iter > 0)
        while live.any():
            # a stopped fit's step is nought
            D = jnp.stack([
                self._direction(G[j], *pairs[j], stored[j], eps)
                if live[j] else jnp.zeros_like(G[j]) for j in range(fits)])
            gd = np.asarray(jnp.sum(G * D, axis=1), np.float64)
            dz = self._logits(D, self._X)
            t = np.ones(fits)
            n_ls = np.zeros(fits, int)

            def values():
                return np.asarray(self._along(
                    jnp.asarray(t, jnp.float32), W, D, z, dz, *data[1:]),
                    np.float64)

            f_new = values()
            while True:
                back = (live & (f_new > f + 1e-4 * t * gd)
                        & (n_ls < max_ls))
                if not back.any():
                    break
                t[back] *= 0.5
                n_ls[back] += 1
                f_new = np.where(back, values(), f_new)
            ok = f_new <= f + 1e-4 * t * gd
            W_new = W + jnp.asarray(t, jnp.float32)[:, None] * D
            f_new, G_new, z_new = values_and_grads(W_new)
            sy = np.asarray(jnp.sum((W_new - W) * (G_new - G), axis=1),
                            np.float64)
            for j in np.flatnonzero(live & (sy > 1e-10)):
                S, Y, rho = pairs[j]
                S.append(W_new[j] - W[j])
                Y.append(G_new[j] - G[j])
                rho.append(1.0 / (sy[j] + eps))
                pairs[j] = (S[-history:], Y[-history:], rho[-history:])
                stored[j] += 1
            keep = jnp.asarray(live)[:, None]
            W, G = jnp.where(keep, W_new, W), jnp.where(keep, G_new, G)
            z = jnp.where(keep[:, :, None], z_new, z)
            f = np.where(live, f_new, f)
            it[live] += 1
            small = np.asarray(jnp.max(jnp.abs(G), axis=1)) <= tol
            live &= ~(small | ~ok | (it >= max_iter))
        return [(W[j], int(it[j])) for j in range(fits)]

    def fold_scores(self, folds, pairs, max_iter, tol, history=10,
                    train_stride=1):
        """The answers of the ``(fold, C)`` fits of ``pairs``: each
        test fold's ``-mean(logloss)``, summed in float64.
        ``train_stride=2`` trains each on every second row of its fold:
        the fault "half the rows left out", for the limits'
        readings."""
        import jax.numpy as jnp

        fits = self.fit_many(
            [(folds[fold][0][::train_stride], C) for fold, C in pairs],
            max_iter, tol, history)
        rows = np.asarray(self._row_loss(
            jnp.stack([w for w, _ in fits]), self._X, self._onehot),
            np.float64)
        return [-float(np.mean(rows[j, folds[fold][1]]))
                for j, (fold, _) in enumerate(pairs)]
