"""
Online scoring with ServingEngine: concurrent small requests served by
dynamic micro-batching over AOT-prewarmed shape buckets.

Counterpart of the reference's deployment story (a pandas UDF scoring
DataFrame partitions — batch-only): here 8 client threads fire
batch-1..16 requests at a registered model and every flush rides one
of a handful of prewarmed compiled programs. Compare the per-request
baseline: each call paying a full `batch_predict` dispatch for a few
rows.

Sample output (CPU backend, 8 virtual devices):
    -- registered clicks@1, buckets [8, 16, 32, 64, 128], 5 programs prewarmed
    -- served 800 requests from 8 threads in 0.72s (1106 req/s)
    -- per-request batch_predict baseline: 71 req/s -> 15.5x
    -- p50 4.9ms  p99 9.6ms  batch fill 0.65  compiles after warmup: 0

Run: python examples/serve/online_scoring.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import threading
import time

import numpy as np
from sklearn.datasets import load_digits

from skdist_tpu.distribute.predict import batch_predict
from skdist_tpu.models import LogisticRegression
from skdist_tpu.parallel import TPUBackend
from skdist_tpu.serve import ServingEngine

N_CLIENTS = 8
REQUESTS_PER_CLIENT = 100


def main():
    X, y = load_digits(return_X_y=True)
    X = (X / 16.0).astype(np.float32)
    model = LogisticRegression(max_iter=60).fit(X, y)
    backend = TPUBackend(reuse_broadcast=True)

    engine = ServingEngine(backend=backend, max_batch_rows=128,
                           max_delay_ms=2.0)
    entry = engine.register("clicks", model,
                            methods=("predict", "predict_proba"))
    print(f"-- registered {entry.spec}, buckets {entry.buckets}, "
          f"{len(entry.buckets)} programs prewarmed")

    streams = []
    for c in range(N_CLIENTS):
        r = np.random.RandomState(100 + c)
        streams.append([
            (int(r.randint(0, len(X) - 16)), int(r.randint(1, 17)))
            for _ in range(REQUESTS_PER_CLIENT)
        ])

    def client(stream):
        for i, n in stream:
            proba = engine.predict_proba(X[i:i + n], timeout_s=30)
            assert proba.shape == (n, 10)

    threads = [threading.Thread(target=client, args=(s,))
               for s in streams]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    served_s = time.perf_counter() - t0
    n_total = N_CLIENTS * REQUESTS_PER_CLIENT
    print(f"-- served {n_total} requests from {N_CLIENTS} threads in "
          f"{served_s:.2f}s ({n_total / served_s:.0f} req/s)")
    # snapshot BEFORE the baseline leg: compiles_after_warmup is a
    # process-global counter, and the baseline's per-request shapes
    # below legitimately compile (that cost is the point of the demo)
    st = engine.stats()

    # baseline: the same request stream, each paying its own dispatch
    base_n = REQUESTS_PER_CLIENT // 4

    def baseline_client(stream):
        for i, n in stream[:base_n]:
            batch_predict(model, X[i:i + n], method="predict_proba",
                          backend=backend)

    threads = [threading.Thread(target=baseline_client, args=(s,))
               for s in streams]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    base_rps = N_CLIENTS * base_n / (time.perf_counter() - t0)
    print(f"-- per-request batch_predict baseline: {base_rps:.0f} req/s "
          f"-> {n_total / served_s / base_rps:.1f}x")

    print(f"-- p50 {st['p50_ms']}ms  p99 {st['p99_ms']}ms  "
          f"batch fill {st['batch_fill_ratio']}  "
          f"compiles after warmup: {st['compiles_after_warmup']}")
    engine.close()


if __name__ == "__main__":
    main()
