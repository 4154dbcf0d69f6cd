"""On-platform sweep for the forest histogram kernel (VERDICT item 3)
AND the packed-CSR matvec kernels (ROADMAP item 4).

Forest leg — two passes on the NOTES benchmark shape (20k x 54, 7
classes, depth 8, 32 bins):

1. RANKING: 20-tree forests across hist_mode x hist_block configs
   (cold + warm walls each);
2. HEADLINE: 100 trees, 2 repeats, for the measured winner, against
   sklearn's multicore CPU engine.

The winner is persisted to ``skdist_tpu/models/hist_calib.json`` via
:func:`hist_calib.record_calibration`, which is exactly what
``hist_mode="auto"`` consults — so running this sweep IS the act of
calibrating ``auto`` for the current platform. Block-size variants are
timed through that same mechanism (write candidate entry, fit under
``auto``) so the sweep exercises the code path users run.

Sparse leg (``--sparse``, or riding along after the forest leg):
micro-benchmarks the packed matvec/rmatvec contraction pair per mode —
``gather`` (XLA gather + scatter-add), ``dense``
(rebuild-once + MXU matmuls), ``pallas`` (the VMEM-rebuild kernels of
``ops/pallas_sparse.py``; only where compiled Pallas targets the
platform — the interpreter is never a candidate) — on the BASELINE
config-3 packed shape, and persists the winner to
``skdist_tpu/models/sparse_calib.json`` via
:func:`sparse.record_matvec_calibration`, which is exactly what
``resolve_matvec_mode()`` (the packed fits' ``'auto'``) consults.

Run ON the chip, as the one process that holds it (no JAX_PLATFORMS
override).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def make_data(n=20000, d=54, k=7, seed=0):
    from bench import make_tabular

    return make_tabular(n, d, k, seed=seed, noise=0.5)


def time_forest(X, y, n_estimators, repeats=2, **kw):
    from skdist_tpu.models.forest import RandomForestClassifier

    walls = []
    for r in range(repeats):
        f = RandomForestClassifier(
            n_estimators=n_estimators, max_depth=8, n_bins=32,
            max_features="sqrt", random_state=r, **kw,
        )
        t0 = time.perf_counter()
        f.fit(X, y)
        walls.append(time.perf_counter() - t0)
    return walls


#: a non-gather mode must beat gather by this factor on the BINARY
#: round trip before the sweep records it as the platform default:
#: gather is today's pinned path (numerics bit-for-bit reproduced by
#: every historical artifact), and flipping the fleet's default
#: contraction for a <2x win trades numeric churn for noise
SPARSE_MIN_WIN = 2.0


def sparse_matvec_sweep(repeats=3):
    """Rank the packed matvec modes on this platform and persist the
    winner to ``sparse_calib.json``. Returns the recorded entry.

    The measured pair is the solver round trip: one ``X @ W`` plus the
    grad through it (``X.T @ r`` — on the pallas path that exercises
    the custom-VJP rmatvec kernel) at the BASELINE config-3 packed
    shape. The CALIBRATING shape is the binary lane (``k=1`` — OvR
    columns and CV fold tasks, the dominant packed workload); the
    joint-multinomial ``k=20`` round trip is recorded alongside as
    evidence (on XLA CPU the k=20 scatter-add is 100-200x slower than
    the rebuilt matmul — the very pathology the pallas kernels exist
    to fix on chip). Each mode runs through the SAME ``LinearOperator``
    interface the fits use."""
    import jax
    import jax.numpy as jnp

    from bench import make_20news_sparse
    from skdist_tpu import sparse as sx
    from skdist_tpu.ops import pallas_interpret

    platform = jax.devices()[0].platform
    X, y = make_20news_sparse(n=4000, d=4096, nnz_row=40, k=20)
    packed = sx.pack_for_fit(X)
    assert packed is not None, "sweep shape must route packed"
    rng = np.random.RandomState(0)

    modes = ["gather", "dense"]
    if not pallas_interpret():
        # off-TPU 'pallas' is the interpreter — never a candidate a
        # CPU calibration should record
        modes.append("pallas")

    ranking = {}  # mode -> {k: wall}
    for mode in modes:
        try:
            op = sx.LinearOperator(packed, fit_intercept=True, mode=mode)
            walls = {}
            for k in (1, 20):
                shape = ((packed.n_cols + 1,) if k == 1
                         else (packed.n_cols + 1, k))
                W = jnp.asarray(rng.randn(*shape).astype(np.float32))

                @jax.jit
                def round_trip(W):
                    def f(w):
                        return jnp.sum(op.matvec(w) ** 2)

                    return jax.value_and_grad(f)(W)

                jax.block_until_ready(round_trip(W))  # compile
                times = []
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    jax.block_until_ready(round_trip(W))
                    times.append(time.perf_counter() - t0)
                walls[f"k{k}"] = round(min(times), 5)
            ranking[mode] = walls
            print(json.dumps({"sparse_matvec": mode, **walls,
                              "platform": platform}), flush=True)
        except Exception as exc:  # one broken mode must not eat the rest
            print(json.dumps({"sparse_matvec": mode,
                              "error": repr(exc)[:300]}), flush=True)
    if not ranking:
        print(json.dumps({"error": "every sparse matvec mode failed"}),
              flush=True)
        return None
    best = min(ranking, key=lambda m: ranking[m]["k1"])
    if (best != "gather" and "gather" in ranking
            and ranking["gather"]["k1"]
            < SPARSE_MIN_WIN * ranking[best]["k1"]):
        best = "gather"  # not a decisive win: keep the pinned default
    entry = sx.record_matvec_calibration(
        platform, best,
        measured={
            "round_trip_s": ranking,
            "min_win": SPARSE_MIN_WIN,
            "shape": [int(X.shape[0]), int(X.shape[1])],
            "m": packed.m,
            "captured_at": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        },
        source="build_tools/tpu_tree_sweep.py sparse_matvec_sweep",
    )
    print(f"# sparse matvec calibration written: {json.dumps(entry)}",
          flush=True)
    return entry


def main():
    import jax

    from skdist_tpu.models import hist_calib

    X, y = make_data()
    platform = jax.devices()[0].platform
    print(f"# platform: {platform} ({jax.devices()})", flush=True)

    # remember any pre-existing calibration so a crash mid-sweep can be
    # diagnosed against what the file said before
    prior = hist_calib.get_calibration(platform)
    if prior:
        print(f"# prior calibration: {json.dumps(prior['measured'])}",
              flush=True)

    # Stage ALL candidate writes in a scratch file: a crash or the
    # watcher's timeout mid-sweep must never leave a half-measured
    # ranking candidate as the committed calibration. Only the final
    # winner (with its full measurement) lands in the real table.
    import tempfile

    scratch = tempfile.NamedTemporaryFile(
        suffix=".hist_calib.json", delete=False)
    scratch.close()
    os.environ[hist_calib.PATH_ENV] = scratch.name

    configs = [
        ("matmul", None),
        ("matmul_sib", None),
        ("pallas", None),
        ("scatter", 8),
        ("scatter", 16),
        ("scatter", 54),
    ]
    if platform == "cpu":
        # off-TPU pallas runs through the interpreter — minutes per
        # tree at this shape, and never a mode auto would pick on cpu
        configs = [c for c in configs if c[0] != "pallas"]
    from skdist_tpu.models.native_forest import native_forest_supported

    if native_forest_supported(32):
        # the host C engine competes on every platform that can build
        # it — on a TPU host it serves LocalBackend/sc=None fits even
        # when the device engine wins the distributed path
        configs.append(("native", None))

    # ---- pass 1: rank with 20-tree forests
    ranking = []
    for mode, block in configs:
        try:
            if mode == "scatter":
                # candidate calibration entry + fit under "auto": the
                # exact path users run, including the block-size lookup
                hist_calib.record_calibration(
                    platform, "scatter", hist_block=block,
                    source="tpu_tree_sweep ranking candidate",
                )
                walls = time_forest(X, y, 20, hist_mode="auto")
            else:
                walls = time_forest(X, y, 20, hist_mode=mode)
        except Exception as exc:  # one broken mode must not eat the rest
            print(json.dumps({
                "config": f"{mode}/block={block}", "error": repr(exc)[:300],
            }), flush=True)
            continue
        rec = {
            "config": f"{mode}/block={block}",
            "mode": mode, "block": block, "n_trees": 20,
            "cold_s": round(walls[0], 2),
            "warm_s": round(min(walls[1:]), 2),
            "platform": platform,
        }
        ranking.append(rec)
        print(json.dumps(rec), flush=True)

    if not ranking:
        print(json.dumps({"error": "every config failed"}), flush=True)
        sys.exit(1)

    best = min(ranking, key=lambda r: r["warm_s"])

    # ---- pass 2: headline 100-tree walls for the winner (still in the
    # scratch table: the committed file is written once, after success)
    hist_calib.record_calibration(
        platform, best["mode"], hist_block=best["block"] or 8,
        source="tpu_tree_sweep winner (headline pending)",
    )
    walls = time_forest(X, y, 100, hist_mode="auto")
    full_s = round(min(walls[1:]), 2)

    # sklearn reference engine (multicore CPU), same workload
    from sklearn.ensemble import RandomForestClassifier as SkRF

    t0 = time.perf_counter()
    SkRF(n_estimators=100, max_depth=8, n_jobs=-1, random_state=0).fit(X, y)
    sk_s = round(time.perf_counter() - t0, 2)

    # all measurements done — write the committed table
    os.environ.pop(hist_calib.PATH_ENV, None)
    os.unlink(scratch.name)
    xla_ranked = [r for r in ranking
                  if r["mode"] in ("scatter", "matmul", "matmul_sib",
                                   "pallas")]
    best_xla = (
        min(xla_ranked, key=lambda r: r["warm_s"]) if xla_ranked else None
    )
    entry = hist_calib.record_calibration(
        platform, best["mode"], hist_block=best["block"] or 8,
        xla_mode=best_xla["mode"] if best_xla else None,
        xla_hist_block=(best_xla["block"] or 8) if best_xla else None,
        measured={
            "winner_100_trees_warm_s": full_s,
            "winner_100_trees_cold_s": round(walls[0], 2),
            "sklearn_njobs_all_100_trees_s": sk_s,
            "ranking_20_trees": {
                r["config"]: r["warm_s"] for r in ranking
            },
            "shape": [20000, 54, 7], "depth": 8, "n_bins": 32,
            "captured_at": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        },
    )
    print(f"# calibration written: {json.dumps(entry)}", flush=True)

    print(json.dumps({
        "metric": "forest 100 trees 20k x 54 (warm wall)",
        "value": full_s, "unit": "s",
        "winner": best["config"],
        "vs_sklearn_njobs_all": round(sk_s / full_s, 2),
        "platform": platform,
    }), flush=True)

    # the sparse matvec leg rides along: one full sweep run calibrates
    # BOTH 'auto' tables (hist_calib.json + sparse_calib.json)
    sparse_matvec_sweep()


if __name__ == "__main__":
    if "--sparse" in sys.argv:
        sparse_matvec_sweep()
    else:
        main()
