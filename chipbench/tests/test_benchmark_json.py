"""BENCHMARK.json against the contract's rules that a test can hold,
and against the files it names."""

import json
import os
import re
import subprocess
import sys

import pytest

from chipbench import run

ROOT = run.ROOT
BENCH = run.load_json("BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_names_units_and_sources():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_every_cell_reports_what_its_metrics_move():
    cells = {w["name"] for w in BENCH["workloads"]}
    configs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert w["config"] in configs
        e2e = {m["name"] for m in run.metrics_of(BENCH, w, "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = run.metrics_of(BENCH, w, "per_layer")
        assert layer, w["name"]
        assert all(m["moves"] in e2e for m in layer)
    for m in BENCH["per_layer"]:
        assert set(m.get("workloads", ())) <= cells
        for name in m.get("workloads", ()):
            cell = next(w for w in BENCH["workloads"] if w["name"] == name)
            assert m["moves"] in {
                e["name"] for e in run.metrics_of(BENCH, cell, "end_to_end")}


def test_every_named_file_is_there():
    for c in BENCH["configs"]:
        config = run.load_json(c["file"])
        assert c["file"].startswith("chipbench/")
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "drivers", config["driver"] + ".py"))
    for w in BENCH["workloads"]:
        run.load_json("chipbench", "traffic", w["traffic"] + ".json")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        spec = run.load_json("chipbench", "metrics", m["name"] + ".json")
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "readers", spec["reader"] + ".py"))


def test_without_a_tpu_the_run_exits_nonzero_and_prints_no_result():
    cell = BENCH["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", cell, "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_an_unknown_workload_is_refused():
    with pytest.raises(SystemExit, match="no workload"):
        run.load_cell("no-such-cell")
