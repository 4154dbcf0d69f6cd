"""Serving-runtime smoke: the online-inference acceptance gate.

Starts a ServingEngine on the CPU mesh (8 virtual devices — the same
harness the unit tier uses), registers the BASELINE config-5 model,
and fires 1k mixed-shape (batch 1..16) requests from 8 threads.
Asserts the serving PR's acceptance criteria:

1. zero compiles after warmup (every shape bucket was AOT-prewarmed at
   registration; steady-state dispatch must be pure cache hits);
2. zero dropped futures — every submitted request resolves;
3. p99 latency under a generous bound (CI machines are noisy; the
   bound catches order-of-magnitude regressions like a lost batch or a
   per-request compile, not scheduler jitter);
4. served outputs BITWISE identical to offline ``batch_predict`` on
   bucket-aligned shapes (same compiled program by construction) and
   allclose on every other shape;
5. >= RATIO x throughput (default 5x) over per-request
   ``batch_predict`` calls from the same 8 threads.

Exit code 0 = pass. Usage:

    python build_tools/serving_smoke.py [--ratio 5.0] [--p99-ms 500]
"""

import argparse
import json
import os
import sys
import threading
import time

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

# pin the CPU mesh BEFORE jax import: the smoke checks the serving
# runtime's mechanism, which is identical on device backends, and
# claims nothing about a chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
)

import numpy as np  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ratio", type=float, default=5.0,
                    help="min served/baseline throughput ratio")
    ap.add_argument("--p99-ms", type=float, default=500.0,
                    help="generous p99 latency bound (ms)")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--requests", type=int, default=125,
                    help="per client; 8 x 125 = 1k total")
    args = ap.parse_args()

    from bench_serving import run_serving_bench

    from skdist_tpu.distribute.predict import batch_predict
    from skdist_tpu.parallel import TPUBackend
    from skdist_tpu.serve import ServingEngine
    from run_all import config5_recipe

    failures = []

    # ---- throughput + steady-state invariants (1k mixed requests) ----
    out = run_serving_bench(
        clients=args.clients, requests_per_client=args.requests,
        scale=0.02,
    )
    stats = out["serving_stats"]
    print(json.dumps(out))

    if out["n_errors"]:
        failures.append(
            f"dropped/failed futures: {out['n_errors']} "
            f"(first: {out['errors'][:2]})"
        )
    if stats["completed"] != stats["requests"]:
        failures.append(
            f"completed {stats['completed']} != submitted "
            f"{stats['requests']}"
        )
    if stats["compiles_after_warmup"] != 0:
        failures.append(
            f"compiles_after_warmup = {stats['compiles_after_warmup']} "
            "(a request shape escaped the prewarmed bucket set)"
        )
    if stats["p99_ms"] is None or stats["p99_ms"] > args.p99_ms:
        failures.append(
            f"p99 {stats['p99_ms']} ms exceeds the {args.p99_ms} ms bound"
        )
    ratio = out["speedup_vs_per_request_batch_predict"]
    if ratio < args.ratio:
        failures.append(
            f"served/baseline throughput {ratio}x below the "
            f"{args.ratio}x acceptance floor"
        )

    # ---- numerical parity: served vs offline batch_predict -----------
    model, Xs, _ = config5_recipe(0.02)
    backend = TPUBackend(reuse_broadcast=True)
    engine = ServingEngine(backend=backend, max_batch_rows=256,
                           max_delay_ms=1.0)
    entry = engine.register("parity", model, methods=("predict_proba",))
    n_slots = backend.n_task_slots
    for bucket in entry.buckets[:3]:
        rows = Xs[:bucket]
        served = engine.predict_proba(rows, timeout_s=30)
        offline = batch_predict(model, rows, method="predict_proba",
                                backend=backend,
                                batch_size=max(1, bucket // n_slots))
        if not np.array_equal(np.asarray(served), np.asarray(offline)):
            failures.append(
                f"bucket {bucket}: served != batch_predict bitwise"
            )
    # off-bucket shapes: same math through a padded program — allclose
    for n in (3, 11):
        served = engine.predict_proba(Xs[:n], timeout_s=30)
        offline = batch_predict(model, Xs[:n], method="predict_proba",
                                backend=backend)
        if not np.allclose(served, offline, atol=1e-6):
            failures.append(f"shape {n}: served !~ batch_predict")
    engine.close()

    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print(f"serving smoke OK: {ratio}x over per-request batch_predict, "
          f"p99 {stats['p99_ms']} ms, 0 post-warmup compiles, "
          "bitwise parity on bucket shapes")
    return 0


if __name__ == "__main__":
    t0 = time.perf_counter()
    rc = main()
    print(f"[serving_smoke] wall {time.perf_counter() - t0:.1f}s")
    sys.exit(rc)
