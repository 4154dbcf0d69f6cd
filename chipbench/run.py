"""
chipbench/run.py — one run of one cell of BENCHMARK.json.

  python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. Everything that belongs to a cell is data found by name:
the workload's entry in ``BENCHMARK.json`` names a configuration
(``chipbench/configs/<config>.json``, which names its driver under
``chipbench/drivers/``) and a traffic mix
(``chipbench/traffic/<traffic>.json``); each per-layer metric is
``chipbench/metrics/<metric>.json``, which names its reader under
``chipbench/readers/``. Nothing here knows a cell's name.

Set-up (imports, data from the seed, one untimed warm-up fit that
compiles or reads the compile cache) is timed from process start and
reported as ``setup_s``; then fits run back to back for ``--seconds``
(``window.run_window``); then the device's peak memory is read, the
program's state dropped, and the comparison with the plain reference
decides ``correct``. The last line of standard output is the result
object. Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: host spans the program writes into the profiler's trace
#: (``skdist_tpu/obs/trace.py`` passthrough); idle gaps are labelled
#: by them
HOST_SPANS = ("round_dispatch", "round_gather", "compile", "rung_eval",
              "block_feed")
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_cell(workload, bench=None):
    """``(bench, cell, config, traffic)`` of a workload name in
    ``BENCHMARK.json`` (or in ``bench``, for the tests)."""
    bench = bench or load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have: {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(entry["file"])
    traffic = load_json("chipbench", "traffic", cell["traffic"] + ".json")
    return bench, cell, config, traffic


def metrics_of(bench, cell, kind):
    """The cell's metric entries of ``end_to_end`` or ``per_layer``: an
    entry without ``workloads`` belongs to every cell that reports the
    end-to-end metric it moves (or, end to end, to every cell)."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if kind == "end_to_end":
        return e2e
    have = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])
            and m["moves"] in have]


def pick_devices(cell, traffic):
    """The chips this cell runs on, or no run at all."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: JAX's first device is "
                     f"{devices[0].platform!r}, not a TPU; no result")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"chipbench: the cell needs {cell['chips']} chip(s); "
                     f"jax.devices() has {len(devices)}; no result")
    devices = devices[:cell["chips"]]
    return devices if traffic["devices"] == "all" else devices[:1]


def memory_peak_bytes(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") or 0
             for d in devices]
    return int(max(peaks))


def read_metric(name, ctx):
    spec = load_json("chipbench", "metrics", name + ".json")
    reader = importlib.import_module("chipbench.readers." + spec["reader"])
    return reader.read(ctx, **spec.get("args", {}))


def run_cell(bench, cell, config, traffic, seed, seconds, trace, devices,
             t_start=None):
    """Everything of a run but the look for a chip; returns the result
    object."""
    from skdist_tpu.parallel import compile_cache, faults

    from chipbench import guards, peaks, trace_reduce, window

    t_start = _T0 if t_start is None else t_start
    cache_dir = compile_cache.enable_disk_cache()
    driver = importlib.import_module("chipbench.drivers." + config["driver"])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"chipbench: {cell['name']} seed={seed} seconds={seconds} "
        f"trace={trace} device={device} compile_cache={cache_dir}")

    state = driver.setup(config, seed, devices)
    n_units = driver.units(state)
    faults.reset_stats()
    snap0 = compile_cache.snapshot()
    warm = guards.guarded(lambda: driver.fit(state), n_units)
    if warm["failed"]:
        raise SystemExit(f"chipbench: the warm-up fit failed: {warm['why']}")
    snap1 = compile_cache.snapshot()
    setup_s = time.perf_counter() - t_start
    say(f"chipbench: set-up {setup_s:.1f}s, compiled in set-up: "
        f"{guards.compile_delta(snap0, snap1)}")

    stopper = None
    if trace:
        stopper = start_trace(float(traffic.get("trace_seconds", 3.0)),
                              float(traffic.get("trace_offset_seconds", 0)))
    fits, elapsed = window.run_window(
        lambda: guards.guarded(lambda: driver.fit(state), n_units), seconds)
    if stopper is not None:
        stopper()
    peak = memory_peak_bytes(devices)
    compiles = guards.compile_delta(snap1, compile_cache.snapshot())
    for f in fits:
        say(f"chipbench: fit {f['t0']:.2f}-{f['t1']:.2f}s units={f['units']} "
            f"failed={f['failed']}" + (f" ({f['why']})" if f["why"] else ""))

    ctx = {
        "config": config, "cell": cell, "fits": fits, "elapsed": elapsed,
        "units_done": window.units_done(fits),
        "setup_s": setup_s, "n_devices": len(devices),
        "device_kind": device["kind"], "peaks": peaks.load(),
        "compiles": compiles, "memory_peak_bytes": peak, "trace": None,
    }
    device["memory_peak_bytes"] = peak
    result = {"attempted": sum(f["units"] for f in fits),
              "failed": sum(f["failed"] for f in fits)}
    if trace and os.path.isdir(TRACE_DIR):
        planes = trace_reduce.load(TRACE_DIR)
        say("chipbench: trace planes: " + "; ".join(
            f"{name} [" + ", ".join(f"{line}:{len(evs)}"
                                    for line, evs in lines[:8]) + "]"
            for name, lines in planes))
        reduced = trace_reduce.reduce(planes, HOST_SPANS)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        if reduced is not None:
            ctx["trace"] = reduced
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    metrics = {}
    for m in metrics_of(bench, cell, "per_layer" if trace else "end_to_end"):
        value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the program's state goes before the reference takes the device
    answers = [f["answer"] for f in fits if f["answer"] is not None]
    for f in fits:
        f["answer"] = None
    warm.clear()
    gc.collect()
    t_ref = time.perf_counter()
    compared = driver.compare(state, answers) if answers else []
    correct = bool(compared) and len(answers) == len(fits) and all(
        c["value"] <= c["limit"] for c in compared)
    say(f"chipbench: reference and comparison took "
        f"{time.perf_counter() - t_ref:.1f}s")
    return {
        "correct": correct, **result, "metrics": metrics, "device": device,
        "fit_seconds": [f["t1"] - f["t0"] for f in fits],
        "compared": {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in compared},
    }


def start_trace(trace_seconds, offset_seconds=0.0):
    """Profile ``trace_seconds`` of the window from ``offset_seconds``
    into it (a fit begins with host work: splitting, placing the data);
    returns what ends the profile if the window ends sooner."""
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    over = threading.Event()

    def profile():
        if over.wait(offset_seconds):
            return
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        over.wait(trace_seconds)
        jax.profiler.stop_trace()

    thread = threading.Thread(target=profile, daemon=True)
    thread.start()

    def finish():
        over.set()
        thread.join()

    return finish


def say(text):
    print(text, file=sys.stderr, flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, config, traffic = load_cell(args.workload)
    if args.trace:
        # read once, when skdist_tpu.obs.trace is first imported
        os.environ["SKDIST_TRACE"] = "1"
        os.environ["SKDIST_TRACE_JAX"] = "1"
    devices = pick_devices(cell, traffic)
    result = run_cell(bench, cell, config, traffic, args.seed, args.seconds,
                      args.trace, devices)
    for name, c in result["compared"].items():
        say(f"compared {name}: {c['value']!r} (limit {c['limit']!r})")
    say(f"correct: {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
