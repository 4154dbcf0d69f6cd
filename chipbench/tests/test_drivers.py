"""The drivers and the rest of a run, at tiny shapes on the CPU: a
sound run reads correct; the control (one precision down) and every
fault a cell can have read NOT correct."""

import copy
import json
import time

import jax
import numpy as np
import pytest

from chipbench import peaks, run
from chipbench.drivers import search


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    """The harness refuses a device kind without published peaks; the
    tests lend the CPU the v5e's so the readers have something to
    divide by (no number of a CPU run is a device metric)."""
    table = peaks.load()
    monkeypatch.setattr(
        peaks, "load", lambda path=None: dict(table, cpu=table["TPU v5 lite"]))


CELL = run.load_json("BENCHMARK.json")["workloads"][0]["name"]


def tiny(**data):
    bench, cell, config, traffic = run.load_cell(CELL)
    config = copy.deepcopy(config)
    config["data"].update(n=1600, d=32)
    config["search"]["C_logspace"] = [-3, 2, 8]
    config["data"].update(data)
    return bench, cell, config, traffic


def drive(devices, trace=0, seed=2 ** 31 + 11, **over):
    bench, cell, config, traffic = tiny()
    cell, traffic = dict(cell, **over.get("cell", {})), dict(
        traffic, **over.get("traffic", {}))
    out = run.run_cell(bench, cell, config, traffic, seed, 0.2, trace,
                       devices, t_start=time.perf_counter())
    json.dumps(out)  # the result line must serialise
    return out


def test_pinned_data_do_not_follow_the_runs_seed():
    bench, cell, config, traffic = tiny()
    a, b = (search.setup(config, seed, jax.devices()[:1]) for seed in (1, 2))
    assert np.array_equal(a["X"], b["X"]) and np.array_equal(a["y"], b["y"])


def test_search_cell_on_one_device():
    out = drive(jax.devices()[:1])
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] % 40 == 0 and out["attempted"] >= 40
    assert set(out["metrics"]) == {"search_fits_per_s", "setup_s"}
    assert out["metrics"]["search_fits_per_s"]["value"] > 0
    assert list(out)[-1] == "compared"


def test_search_traced_on_a_1d_mesh_of_four_devices():
    """The path a four-chip cell would take (a traffic mix whose
    ``devices`` is ``all``), and every reader of the traced run."""
    out = drive(jax.devices()[:4], trace=1, cell={"chips": 4},
                traffic={"devices": "all"})
    assert out["correct"] and out["failed"] == 0
    assert out["device"]["count"] == 4
    # no device plane in a CPU trace: what reads the trace says nothing
    assert "device_idle_pct.search" not in out["metrics"]
    for name in ("host_outside_rounds_pct.search", "device_wait_pct.search",
                 "dispatch_ms_per_round.search", "lbfgs_mfu_pct.search",
                 "window_compiles.search"):
        assert name in out["metrics"], name
    assert out["metrics"]["window_compiles.search"]["value"] == 0


def broken(monkeypatch, driver, breaker):
    real = driver.fit

    def fit(state):
        failed, stats, answer = real(state)
        return failed, stats, breaker(state, answer)

    monkeypatch.setattr(driver, "fit", fit)


def untouched_weights(state, scores):
    # what a solver that hands back its zero start scores everywhere
    return np.full_like(scores, -np.log(state["config"]["data"]["k"]))


def half_the_rows(state, scores):
    half = dict(state, X=state["X"][::2], y=state["y"][::2])
    return search_fit(half)[2]


def one_answer_altered(state, scores):
    scores = scores.copy()
    scores[3, 1] += 5e-3
    return scores


def shards_never_gathered(state, scores):
    scores = scores.copy()
    scores[len(scores) // 4:] = 0.0  # devices 1-3 of the tasks mesh
    return scores


search_fit = search.fit


@pytest.mark.parametrize("breaker", [
    untouched_weights, half_the_rows, one_answer_altered,
    shards_never_gathered])
def test_search_faults_read_not_correct(monkeypatch, breaker):
    broken(monkeypatch, search, breaker)
    out = drive(jax.devices()[:1])
    assert out["failed"] == 0 and not out["correct"], out["compared"]


def test_search_control_reads_not_correct():
    """The program's own bfloat16 path against the float32 reference."""
    bench, cell, config, traffic = tiny()
    state = search.setup(config, 7, jax.devices()[:1])
    sound = search.compare(state, [search.fit(state)[2]])
    control = search.compare(state, search.control_answers(state))
    assert all(c["value"] <= c["limit"] for c in sound), sound
    assert any(c["value"] > c["limit"] for c in control), control


def test_a_fit_that_raises_fails_all_its_units(monkeypatch):
    def fit(state):
        raise RuntimeError("boom")

    monkeypatch.setattr(search, "fit", fit)
    bench, cell, config, traffic = tiny()
    with pytest.raises(SystemExit, match="warm-up fit failed"):
        run.run_cell(bench, cell, config, traffic, 1, 0.1, 0,
                     jax.devices()[:1], t_start=time.perf_counter())
