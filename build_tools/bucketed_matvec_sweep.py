"""
Record the packed matvec mode for a BUCKETED matrix on this platform,
at the text deployment's real shape (the benchmark's pinned corpus:
11,314 x 130,107, 1.79 M stored elements, 20 classes), and write the
platform's entry of ``sparse_calib.json`` (to the path
``SKDIST_SPARSE_CALIB_PATH`` names, where set).

Measured: the solver's round trip — the loss through ``X @ W`` and its
gradient through ``X.T @ r`` — by the one ``LinearOperator`` the fits
use, for a batch of 8 lanes (``vmap``); and, for the record of why the
representation is what it is, one lane's round trip through two forms
it could have been: a flat COO with segment sums, and max-row gathers
whose transpose XLA derives (a scatter-add). A third, the Pallas
rebuild kernels of ``ops/pallas_sparse`` bucket by bucket, took 288 ms
a lane on a v5e against the gathers' 3.6 (their work follows n x d;
the committed ``"tpu"`` entry keeps that reading) and is no longer a
form a ``BucketedX`` has, so it is not measured again here.

    python build_tools/bucketed_matvec_sweep.py [--lanes 8]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def timed(fn, *args, repeats=3):
    import jax

    jax.block_until_ready(fn(*args))
    walls = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        walls.append(time.perf_counter() - t0)
    return round(min(walls), 5)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", type=int, default=8)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from chipbench import datagen_text
    from skdist_tpu import sparse as sx

    platform = jax.devices()[0].platform
    n, d, k = 11314, 130107, 20
    X, y = datagen_text.bag_of_words(20, n, d, k, 1787565)
    B = jax.tree_util.tree_map(jnp.asarray, sx.pack_for_fit(X))
    onehot = jax.nn.one_hot(jnp.asarray(y), k, dtype=jnp.float32)
    rng = np.random.RandomState(0)
    W = jnp.asarray(rng.randn(args.lanes, (d + 1) * k).astype(np.float32)
                    * 0.01)

    def loss_through(matvec):
        def loss(wflat):
            z = matvec(wflat)
            return jnp.sum(jax.nn.logsumexp(z, axis=1)
                           - jnp.sum(onehot * z, axis=1))
        return loss

    op = sx.LinearOperator(B, True, mode="gather")
    ranking = {"gather": timed(jax.jit(jax.vmap(jax.value_and_grad(
        loss_through(lambda w: op.matvec(w.reshape(d + 1, k)))))), W)}
    print(json.dumps({"mode": "gather", "lanes": args.lanes,
                      "round_trip_s": ranking["gather"]}), flush=True)

    # one lane through the forms the representation could have been
    coo = X.tocoo()
    rows, cols = jnp.asarray(coo.row), jnp.asarray(coo.col)
    vals = jnp.asarray(coo.data)

    def coo_matvec(wflat):
        Wm = wflat.reshape(d + 1, k)
        return jax.ops.segment_sum(vals[:, None] * Wm[cols], rows, n,
                                   indices_are_sorted=True) + Wm[d]

    other = {"coo_segment_sum": timed(
        jax.jit(jax.value_and_grad(loss_through(coo_matvec))), W[0])}
    other["bucketed_gather_one_lane"] = timed(
        jax.jit(jax.value_and_grad(loss_through(
            lambda w: sx.LinearOperator(B, True).matvec(
                w.reshape(d + 1, k))))), W[0])
    # the rows bucketed, the transpose left to autodiff (scatter-add)
    def scatter_matvec(wflat):
        Wm = wflat.reshape(d + 1, k)
        out = [sx.packed_matvec(i.reshape(-1, i.shape[2]),
                                v.reshape(-1, i.shape[2]), Wm)
               for i, v in B.rows]
        return jnp.concatenate(out)[B.inv] + Wm[d]

    try:
        other["bucketed_rows_scatter_transpose"] = timed(
            jax.jit(jax.value_and_grad(loss_through(scatter_matvec))), W[0])
    except Exception as exc:
        other["bucketed_rows_scatter_transpose"] = repr(exc)[:200]
    print(json.dumps({"one_lane_round_trip_s": other}), flush=True)

    entry = sx.record_matvec_calibration(
        platform, "gather",
        measured={
            "round_trip_s": ranking, "lanes": {"gather": args.lanes},
            "one_lane_round_trip_s": other,
            "shape": [n, d], "nnz": int(X.nnz), "classes": k,
            "slots": B.slots,
            "device_kind": jax.devices()[0].device_kind,
            "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()),
        },
        source="build_tools/bucketed_matvec_sweep.py",
    )
    print("# calibration written: " + json.dumps(entry), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
