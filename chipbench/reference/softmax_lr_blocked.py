"""
Plain reference for the multinomial grid search over a dense matrix
that fills most of the chip: the solver of ``reference/softmax_lr.py``
(:meth:`SoftmaxLR.fit_many`, L-BFGS written out plainly, vectors in
float64 on the host), with the loss and its gradient summed over X in
BLOCKS of rows, so that 6.3 GB of X and the logits of the fits that
advance side by side fit the device together — a block's logits and
what its gradient keeps are dropped before the next block's are made.

Float32 on the device, the product at ``highest`` (``"high"``: three
bfloat16 passes, for the control). A block's product is one matmul
against the weights of all fits side by side, class after class, so
every class's logits are a ``(rows, fits)`` array and the softmax runs
across ten of those; the sums over rows are float32 sums block by
block, added up block after block. It imports nothing of
``skdist_tpu`` and takes nothing the program made.
"""

import functools

import numpy as np

from chipbench.reference.softmax_lr import SoftmaxLR


def block_count(n, block_rows):
    """The fewest equal blocks of at most ``block_rows`` rows."""
    count = max(1, -(-n // block_rows))
    while n % count:
        count += 1
    return count


class BlockedSoftmaxLR(SoftmaxLR):
    """``SoftmaxLR`` for ``k > 2`` classes whose three device functions
    walk X block by block; the solver, the scoring and the folds are
    the parent's."""

    def __init__(self, X, y, n_classes, precision="highest",
                 block_rows=125_000):
        import jax
        import jax.numpy as jnp

        if precision not in ("highest", "high"):
            raise ValueError(f"unknown precision {precision!r}")
        if int(n_classes) <= 2:
            raise ValueError("the blocked reference is multinomial")
        self.n, self.d = X.shape
        self.k = int(n_classes)
        n, d, k = self.n, self.d, self.k
        blocks = block_count(n, block_rows)
        rows = n // blocks
        # (blocks, rows, ...) views of the data, cut on the host where
        # that moves nothing: a block is a slice. Block by block to the
        # device too, each written into the whole in place: ONE
        # transfer of 6.27 GB took 33 s on the v5e where 3 GB take
        # 0.4 s, and beside what the program's last fit still holds a
        # second whole does not fit (PERF.md section 7)
        write = jax.jit(lambda whole, block, i: whole.at[i].set(block),
                        donate_argnums=0)
        self._X = jnp.zeros((blocks, rows, d), jnp.float32)
        for i, block in enumerate(
                np.asarray(X, np.float32).reshape(blocks, rows, d)):
            self._X = write(self._X, jnp.asarray(block), i)
        self._y = jnp.asarray(
            np.asarray(y, np.int32).reshape(blocks, rows))

        def row_losses(W, Xb, yb):
            """``(rows, fits)`` log-losses of a block under the weights
            ``W`` of ``(fits, d + 1, k)``."""
            fits = W.shape[0]
            # column c * fits + j: class c of fit j
            A = jnp.transpose(W[:, :d, :], (1, 2, 0)).reshape(d, k * fits)
            Z = jnp.matmul(Xb, A, precision=precision)
            zs = [Z[:, c * fits:(c + 1) * fits] + W[:, d, c]
                  for c in range(k)]
            top = functools.reduce(jnp.maximum, zs)
            lse = top + jnp.log(sum(jnp.exp(z - top) for z in zs))
            own = sum(jnp.where((yb == c)[:, None], z, 0.0)
                      for c, z in enumerate(zs))
            return lse - own

        def block_sums(wflat, Xb, yb, mb):
            """Each fit's masked sum over one block (and their total,
            which is what a gradient is taken of: the fits share
            nothing, so its gradient is theirs side by side)."""
            per_fit = jnp.sum(
                mb.T * row_losses(wflat.reshape(-1, d + 1, k), Xb, yb),
                axis=0)
            return jnp.sum(per_fit), per_fit

        def penalty(wflat, inv_c):
            W = wflat.reshape(-1, d + 1, k)[:, :d]
            return 0.5 * inv_c * jnp.sum(W * W, axis=(1, 2))

        def by_block(masks):
            return jnp.transpose(
                masks.reshape(masks.shape[0], blocks, rows), (1, 0, 2))

        @jax.jit
        def values(wflat, X, y, masks, inv_c):
            def one(acc, block):
                return acc + block_sums(wflat, *block)[1], None

            total, _ = jax.lax.scan(
                one, jnp.zeros(wflat.shape[0], jnp.float32),
                (X, y, by_block(masks)))
            return total + penalty(wflat, inv_c)

        @jax.jit
        def values_and_grads(wflat, X, y, masks, inv_c):
            def one(acc, block):
                (_, f), g = jax.value_and_grad(block_sums, has_aux=True)(
                    wflat, *block)
                return (acc[0] + f, acc[1] + g), None

            (f, g), _ = jax.lax.scan(
                one, (jnp.zeros(wflat.shape[0], jnp.float32),
                      jnp.zeros_like(wflat)), (X, y, by_block(masks)))
            g_reg = jax.grad(lambda w: jnp.sum(penalty(w, inv_c)))(wflat)
            return f + penalty(wflat, inv_c), g + g_reg

        @jax.jit
        def rows_of(wflat, X, y):
            return jax.lax.map(
                lambda block: row_losses(
                    wflat.reshape(1, d + 1, k), *block)[:, 0],
                (X, y)).reshape(n)

        self._values = lambda W, m, c: values(W, self._X, self._y, m, c)
        self._values_and_grads = lambda W, m, c: values_and_grads(
            W, self._X, self._y, m, c)
        self._row_loss = lambda w: rows_of(w, self._X, self._y)


def sampled_fold_scores(ref, folds, pairs, Cs, est, train_stride=1,
                        batches=1):
    """The answers of the ``(candidate, fold)`` pairs, refitted in
    ``batches`` batches one after another (side by side within one)."""
    jobs = [(f, Cs[c]) for c, f in pairs]
    step = -(-len(jobs) // batches)
    return np.array([
        score for at in range(0, len(jobs), step)
        for score in ref.fold_scores(
            folds, jobs[at:at + step], est["max_iter"], est["tol"],
            est["history"], train_stride)])
