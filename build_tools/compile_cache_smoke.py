"""Persistent compile-cache smoke: two FRESH processes, one cache dir.

Runs ``bench.py --quick`` twice in separate subprocesses with
``JAX_COMPILATION_CACHE_DIR`` pointed at a scratch directory and
asserts the acceptance criterion of the pipelined-rounds/compile-cache
PR: the SECOND process's cold wall must drop to <= RATIO (default 0.5)
of the first's, because every XLA program is served from the on-disk
cache instead of being compiled. Pinned to the CPU backend: this
checks the cache's mechanism, which is identical on device backends,
and claims nothing about a chip.

Exit code 0 = pass. Usage:

    python build_tools/compile_cache_smoke.py [--ratio 0.5]
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BENCH = os.path.join(REPO, "bench.py")


def run_quick(cache_dir):
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["JAX_PLATFORMS"] = "cpu"
    # default single CPU device: XLA compiles the UNSHARDED program
    # (the expensive one — sharded per-device shapes compile faster),
    # which is the compile-dominated regime the cache exists for
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, BENCH, "--quick"], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=900,
    )
    if proc.returncode != 0:
        print(proc.stdout[-3000:])
        print(proc.stderr[-2000:], file=sys.stderr)
        raise SystemExit(f"bench --quick failed rc={proc.returncode}")
    payload = None
    for ln in proc.stdout.splitlines():
        if ln.startswith("{"):
            try:
                payload = json.loads(ln)
            except ValueError:
                pass
    if payload is None:
        raise SystemExit("bench --quick printed no JSON line")
    return payload


def attempt(ratio):
    cache_dir = tempfile.mkdtemp(prefix="skdist_cc_smoke_")
    try:
        p1 = run_quick(cache_dir)
        p2 = run_quick(cache_dir)
        cold1 = p1["aux"]["cold_wall_s"]
        cold2 = p2["aux"]["cold_wall_s"]
        cc2 = p2["aux"].get("compile_cache", {})
        entries = {
            f for f in os.listdir(cache_dir) if f.endswith("-cache")
        }
        print(json.dumps({
            "first_cold_wall_s": cold1,
            "second_cold_wall_s": cold2,
            "ratio": round(cold2 / cold1, 3) if cold1 else None,
            "target_ratio": ratio,
            "second_process_compile_cache": cc2,
            "cache_entries": len(entries),
        }, indent=1))
        if not entries:
            raise SystemExit(
                "FAIL: the first process wrote no cache entries — the "
                "on-disk compile cache is not wired up at all"
            )
        return cold2 <= ratio * cold1, cold1, cold2
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)


def main():
    ratio = 0.5
    if "--ratio" in sys.argv:
        ratio = float(sys.argv[sys.argv.index("--ratio") + 1])
    # wall-clock smoke on a shared host: one retry (fresh cache dir)
    # absorbs CPU-contention noise; a REAL cache regression fails both
    for attempt_no in (1, 2):
        ok, cold1, cold2 = attempt(ratio)
        if ok:
            print("COMPILE CACHE SMOKE: PASS")
            return
        print(f"[attempt {attempt_no}] ratio {cold2 / cold1:.3f} > "
              f"{ratio}; retrying" if attempt_no == 1 else "")
    raise SystemExit(
        f"FAIL: second-process cold wall {cold2:.2f}s is not <= "
        f"{ratio} x first-process cold wall {cold1:.2f}s in either "
        "attempt — the on-disk compile cache is not being reused"
    )


if __name__ == "__main__":
    main()
