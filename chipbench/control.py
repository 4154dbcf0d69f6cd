"""
chipbench/control.py — the readings a search cell's limits are set from.

  python3 chipbench/control.py --workload <name> --data-seeds 20,21 --out chiprun_out/table

One process on the chip, at the cell's own size. For each data seed (the
configuration's pinned one, and others to see how far the readings
depend on the data) it writes ``<out>_<seed>.npz`` with, for EVERY
(candidate, fold) of the grid: the program's answer (``program``), the
answer of the program's own lower-precision path (``bf16``:
``matmul_dtype="bfloat16"``), the plain reference's (``reference``),
the reference's at ``high``, the step below the configuration's
``highest`` (``high``: the control where the program has no such
path), and the reference's trained on every second row (``half``: the
fault "half the rows left out"). The benchmark's own runs never call
this file.
"""

import argparse
import gc
import importlib
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
    ap.add_argument("--data-seeds", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    bench, cell, config, traffic = run.load_cell(args.workload)
    devices = run.pick_devices(cell, traffic)
    from skdist_tpu.parallel import compile_cache

    compile_cache.enable_disk_cache()
    driver = importlib.import_module("chipbench.drivers." + config["driver"])
    for data_seed in (int(s) for s in args.data_seeds.split(",")):
        config = dict(config, data=dict(config["data"], seed=data_seed))
        state = driver.setup(config, data_seed, devices)
        out, took = {}, {}

        def timed(name, make):
            t0 = time.perf_counter()
            out[name] = make()
            took[name] = round(time.perf_counter() - t0, 1)
            gc.collect()

        def program():
            record = guards.guarded(lambda: driver.fit(state),
                                    driver.units(state))
            if record["failed"]:
                raise SystemExit(f"the program's fit failed: {record['why']}")
            return record["answer"]

        timed("program", program)
        timed("bf16", lambda: driver.control_answers(state)[0])
        for name, kwargs in (("reference", {}),
                             ("high", {"precision": "high"}),
                             ("half", {"train_stride": 2})):
            timed(name, lambda: driver.reference_scores(state, **kwargs))
        np.savez(f"{args.out}_{data_seed}.npz", **out)
        ref = out["reference"]
        print(f"data seed {data_seed}: seconds {took}; |gap| from the "
              "reference over all answers, median / widest / least: "
              + "; ".join(
                  f"{name} {np.median(np.abs(out[name] - ref)):.3g} / "
                  f"{np.max(np.abs(out[name] - ref)):.3g} / "
                  f"{np.min(np.abs(out[name] - ref)):.3g}"
                  for name in ("program", "bf16", "high", "half")),
              flush=True)
        del state
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
