"""On-platform sweep for the forest histogram kernel (VERDICT item 3).

Two passes on the NOTES benchmark shape (20k x 54, 7 classes, depth 8,
32 bins):

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

Run ON the chip, as the one process that holds it (no JAX_PLATFORMS
override).
"""

import json
import os
import sys
import time

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


if __name__ == "__main__":
    main()
