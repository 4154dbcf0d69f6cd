"""
Plain reference for the multinomial grid search over a dense matrix
that NO single device holds: the solver of ``reference/softmax_lr.py``
(:meth:`SoftmaxLR.fit_many`, L-BFGS written out plainly, vectors in
float64 on the host) over rows cut into as many contiguous PARTS as
devices are given. Each part is a ``BlockedSoftmaxLR`` of its own on
its own device — its rows there in blocks, its loss and gradient summed
block by block in float32 by that device's own compiled functions —
and what the parts answer is added up on the HOST, in float64: every
part is dispatched before any is read, so the devices run side by side.

There is no mesh here, no ``shard_map`` and no collective: nothing but
``jax.device_put`` to a device and ``jit`` on it, so that what the
program's partitioner inserted between the chips is held against an
answer that crossed no chip boundary. Float32 on the devices, the
product at ``highest`` (``"high"``: three bfloat16 passes, for the
control). With one part on one device it IS ``BlockedSoftmaxLR``: the
same functions, the same digits. It imports nothing of ``skdist_tpu``
and takes nothing the program made.
"""

import numpy as np

from chipbench.reference.softmax_lr import SoftmaxLR
from chipbench.reference.softmax_lr_blocked import (  # noqa: F401
    BlockedSoftmaxLR, sampled_fold_scores,
)


def part_bounds(n, parts):
    """``[(start, stop)]`` of ``parts`` contiguous runs of ``n`` rows,
    as equal as they come (the first ``n % parts`` one row longer)."""
    cuts = [n * i // parts for i in range(parts + 1)]
    return list(zip(cuts[:-1], cuts[1:]))


class RowShardedSoftmaxLR(SoftmaxLR):
    """``SoftmaxLR`` for ``k > 2`` classes over row parts, one a
    device; the solver, the scoring and the folds are the parent's."""

    def __init__(self, X, y, n_classes, devices, precision="highest",
                 block_rows=125_000):
        import jax

        self.n, self.d = X.shape
        self.k = int(n_classes)
        self._devices = list(devices)
        self._bounds = part_bounds(self.n, len(self._devices))
        y = np.asarray(y)
        self._parts = []
        for dev, (a, b) in zip(self._devices, self._bounds):
            # a part's rows are a slice of X: a view; built with its
            # device the default one, then COMMITTED to it, so that
            # every call on it runs there
            with jax.default_device(dev):
                part = BlockedSoftmaxLR(X[a:b], y[a:b], n_classes,
                                        precision, block_rows)
            part._X = jax.device_put(part._X, dev)
            part._y = jax.device_put(part._y, dev)
            self._parts.append(part)
        # the fits' row masks, cut and placed once a batch (``fit_many``
        # hands the same array to every evaluation of a batch)
        self._masks_of = (None, None)

        def on_parts(call, W, masks=None, inv_c=None):
            """``call(part, W, masks, inv_c)`` on every part, all
            dispatched before the first is read. The penalty is the
            first part's alone: the others take ``1/C = 0``."""
            cut = self._cut(masks) if masks is not None else None
            W, inv_c = np.asarray(W), (
                None if inv_c is None else np.asarray(inv_c))
            pending = []
            for i, (part, dev) in enumerate(zip(self._parts,
                                                self._devices)):
                args = [jax.device_put(W, dev)]
                if cut is not None:
                    args += [cut[i], jax.device_put(
                        inv_c if i == 0 else np.zeros_like(inv_c), dev)]
                pending.append(call(part, *args))
            return pending

        def total(results):
            return sum(np.asarray(r, np.float64) for r in results)

        def values(W, masks, inv_c):
            return total(on_parts(
                lambda p, *a: p._values(*a), W, masks, inv_c))

        def values_and_grads(W, masks, inv_c):
            both = on_parts(
                lambda p, *a: p._values_and_grads(*a), W, masks, inv_c)
            return total(f for f, _ in both), total(g for _, g in both)

        def rows_of(w):
            return np.concatenate([
                np.asarray(r) for r in on_parts(
                    lambda p, w: p._row_loss(w), w)])

        self._values = values
        self._values_and_grads = values_and_grads
        self._row_loss = rows_of

    def _cut(self, masks):
        """``masks`` of ``(fits, n)`` as one ``(fits, part rows)`` array
        a device."""
        import jax

        key, cut = self._masks_of
        if key is not masks:
            host = np.asarray(masks)
            cut = [jax.device_put(host[:, a:b], dev)
                   for dev, (a, b) in zip(self._devices, self._bounds)]
            self._masks_of = (masks, cut)
        return cut
