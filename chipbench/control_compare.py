"""
chipbench/control_compare.py — a cell's controls and planted faults
through the cell's own comparison, at the cell's own size and sample.

  python3 chipbench/control_compare.py --workload search-20news130k \
      [--seed N] [--out chiprun_out/control_compare.json] \
      [--rows sound,bf16,...] [--every-pair BATCHES]

One process on the chip. It fits the program once (``sound``) and its
own lower-precision path once (``bf16``: ``matmul_dtype="bfloat16"``),
drops both, and asks the driver's plain reference for the pairs that
``--seed`` draws: as the configuration states it, at ``high`` (the
nearest precision below the configuration's ``highest``; the reference
in the program's place) and trained on every second row (the fault
"half the rows left out"). The fault "weights never moved" is the
answer of a model left at its start, ``-log k`` for every fold. Where
the driver's reference can refit its pairs in batches, the reference in
two batches is a second sound answer (the same float32 solver summing
at another width): what it reads is the solver's own sensitivity to
rounding, which a limit has to leave room for. Each
is then held against the reference by ``driver.compare`` —
the function and limits that decide ``correct`` in a run of the cell —
and one JSON line says what it read. ``control.py`` (which may not
change) writes every answer of the grid instead and takes its medians
over all of them, so it prints ``nan`` for a driver that refits a
sample. ``--rows`` keeps some of the rows; ``--every-pair N`` sets the
sample aside and refits EVERY (candidate, fold) pair, in N batches (all
side by side do not fit beside the reference's dense X): the table from
which what any ``--seed`` would draw can be worked out. The benchmark's
own runs never call this file.
"""

import argparse
import gc
import importlib
import inspect
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    from chipbench import guards, run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 7)
    ap.add_argument("--out")
    ap.add_argument("--rows", default="sound,bf16,high,half_the_rows,"
                    "reference_in_two_batches,weights_never_moved")
    ap.add_argument("--every-pair", type=int, default=0, metavar="BATCHES")
    args = ap.parse_args(argv)
    wanted = set(args.rows.split(","))
    bench, cell, config, traffic = run.load_cell(args.workload)
    batched = {}
    if args.every_pair:
        config = dict(config, compare=dict(config["compare"], sample=None))
        batched = {"batches": args.every_pair}
    devices = run.pick_devices(cell, traffic)
    from skdist_tpu.parallel import compile_cache

    compile_cache.enable_disk_cache()
    driver = importlib.import_module("chipbench.drivers." + config["driver"])
    state = driver.setup(config, args.seed, devices)
    answers, took = {}, {}

    def timed(name, make):
        if name not in wanted and name != "reference":
            return
        t0 = time.perf_counter()
        answers[name] = np.asarray(make(), dtype=np.float64)
        took[name] = round(time.perf_counter() - t0, 1)
        gc.collect()

    def program():
        record = guards.guarded(lambda: driver.fit(state),
                                driver.units(state))
        if record["failed"]:
            raise SystemExit(f"the program's fit failed: {record['why']}")
        return record["answer"]

    timed("sound", program)
    timed("bf16", lambda: driver.control_answers(state)[0])
    timed("reference", lambda: driver.reference_scores(state, **batched))
    timed("high", lambda: driver.reference_scores(
        state, precision="high", **batched))
    timed("half_the_rows", lambda: driver.reference_scores(
        state, train_stride=2, **batched))
    if ("batches" in inspect.signature(driver.reference_scores).parameters
            and not batched):
        timed("reference_in_two_batches",
              lambda: driver.reference_scores(state, batches=2))
    if "weights_never_moved" in wanted:
        answers["weights_never_moved"] = np.full_like(
            answers["reference"], -np.log(config["data"]["k"]))
    # the comparison asks for the reference once more each time: it has
    # just been computed, for the same state
    want = answers.pop("reference")
    real, driver.reference_scores = (driver.reference_scores,
                                     lambda state: want)
    rows = {}
    try:
        for name, scores in answers.items():
            compared = driver.compare(state, [scores])
            rows[name] = {
                "correct": all(c["value"] <= c["limit"] for c in compared),
                "compared": {c["name"]: {"value": c["value"],
                                         "limit": c["limit"]}
                             for c in compared},
            }
            print(json.dumps({"answers": name, **rows[name]}), flush=True)
    finally:
        driver.reference_scores = real
    print(json.dumps({"seconds": took, "seed": args.seed,
                      "pairs": int(np.isfinite(want).sum())}), flush=True)
    if args.out:
        def table(a):
            # (candidates, folds); null where no pair was drawn
            return [[float(v) if np.isfinite(v) else None for v in row]
                    for row in a]

        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({
                "workload": args.workload, "seed": args.seed,
                "data_seed": config["data"].get("seed"),
                "seconds": took, "rows": rows, "reference": table(want),
                "answers": {k: table(v) for k, v in answers.items()},
            }, f, indent=1)
    sound = ("sound", "reference_in_two_batches")
    ok = "sound" in rows and all(
        row["correct"] == (name in sound) for name, row in rows.items())
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
