"""The sparse search driver and its plain reference at a small size on
the CPU: a sound run reads correct; the control (the program's
bfloat16 path) and the planted faults read NOT correct. The limits
here are this size's own (400 rows a test fold, both solvers float32);
the cell's are read on the chip."""

import copy
import json
import time

import jax
import numpy as np
import pytest

from chipbench import peaks, run
from chipbench.drivers import search_sparse

CELL = "search-20news130k"


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    table = peaks.load()
    monkeypatch.setattr(
        peaks, "load", lambda path=None: dict(table, cpu=table["TPU v5 lite"]))


def small():
    bench, cell, config, traffic = run.load_cell(CELL)
    config = copy.deepcopy(config)
    # this size's own data seed too: its limits were read on it
    config["data"].update(n=600, d=30000, nnz=36000, k=4, len_cap=3000,
                          topic_terms=100, len_sigma=1.6, seed=20)
    config["estimator"]["max_iter"] = 40
    config["search"]["C_logspace"] = [-2, 2, 5]
    config["compare"] = {"sample": 7, "limits": {"ll_gap_median": 2e-4,
                                                 "ll_gap_max": 2e-3}}
    return bench, cell, config, traffic


@pytest.fixture
def spans():
    """The program's spans on for one test (a traced run of the
    benchmark turns them on through the environment, before import)."""
    from skdist_tpu.obs import trace as obs_trace

    was = obs_trace.enabled()
    obs_trace.set_enabled(True)
    obs_trace.clear()
    yield
    obs_trace.set_enabled(was)


def drive(trace=0, seed=2 ** 31 + 29):
    bench, cell, config, traffic = small()
    out = run.run_cell(bench, cell, config, traffic, seed, 0.2, trace,
                       jax.devices()[:1], t_start=time.perf_counter())
    json.dumps(out)
    return out


def test_the_seed_draws_the_sample_not_the_data():
    bench, cell, config, traffic = small()
    a, b = (search_sparse.setup(config, seed, jax.devices()[:1])
            for seed in (1, 2 ** 31 + 2))
    assert (a["X"] != b["X"]).nnz == 0 and np.array_equal(a["y"], b["y"])
    pa, pb = search_sparse.sample_pairs(a), search_sparse.sample_pairs(b)
    assert pa != pb and len(pa) == len(pb) == 7
    # every C is refitted, whatever the seed draws
    assert {c for c, _ in pa} == {c for c, _ in pb} == set(range(5))
    assert len(set(pa)) == 7
    # at the cell's own sample every C is refitted at two folds
    full = dict(a, config=run.load_cell(CELL)[2])
    full["Cs"] = list(range(10))
    counts = np.bincount([c for c, _ in search_sparse.sample_pairs(full)])
    assert list(counts) == [2] * 10


def test_sparse_search_cell_traced_reads_correct_and_its_metrics(spans):
    out = drive(trace=1)
    assert out["correct"] and out["failed"] == 0, out["compared"]
    assert out["attempted"] % 25 == 0
    for name in ("packed_fill_pct.search", "pack_s_per_fit.search",
                 "lbfgs_sparse_mfu_pct.search", "window_compiles.search",
                 "dispatch_ms_per_round.search"):
        assert name in out["metrics"], name
    # the dense count of work does not read this cell; no memory
    # counter on a CPU, so no estimate against it
    assert "lbfgs_mfu_pct.search" not in out["metrics"]
    assert "round_mem_estimate_pct.search" not in out["metrics"]
    assert 0 < out["metrics"]["packed_fill_pct.search"]["value"] <= 100
    assert 0 < out["metrics"]["pack_s_per_fit.search"]["value"] < 5
    assert out["metrics"]["window_compiles.search"]["value"] == 0


def untouched_weights(state, scores):
    return np.full_like(scores, -np.log(state["config"]["data"]["k"]))


def half_the_rows(state, scores):
    half = dict(state, X=state["X"][::2], y=state["y"][::2])
    return search_fit(half)[2]


search_fit = search_sparse.fit


@pytest.mark.parametrize("breaker", [untouched_weights, half_the_rows])
def test_sparse_search_faults_read_not_correct(monkeypatch, breaker):
    real = search_sparse.fit

    def fit(state):
        failed, stats, answer = real(state)
        return failed, stats, breaker(state, answer)

    monkeypatch.setattr(search_sparse, "fit", fit)
    out = drive()
    assert out["failed"] == 0 and not out["correct"], out["compared"]


def test_sparse_search_control_reads_not_correct():
    """The program's own bfloat16 path against the float32 reference."""
    bench, cell, config, traffic = small()
    state = search_sparse.setup(config, 7, jax.devices()[:1])
    sound = search_sparse.compare(state, [search_sparse.fit(state)[2]])
    control = search_sparse.compare(
        state, search_sparse.control_answers(state))
    assert all(c["value"] <= c["limit"] for c in sound), sound
    assert any(c["value"] > c["limit"] for c in control), control


def test_control_compare_reads_the_cell_through_its_own_comparison(
        monkeypatch, tmp_path, capsys):
    """``control_compare.py`` at the small size: the sound program reads
    correct, the bfloat16 path and both faults do not, each through
    ``search_sparse.compare`` at the configuration's sample. (``high``
    is the chip's three-pass product; a CPU computes it as ``highest``,
    so here it reads what the reference reads against itself.)"""
    from chipbench import control_compare

    loaded = small()
    monkeypatch.setattr(run, "load_cell", lambda name: loaded)
    monkeypatch.setattr(run, "pick_devices",
                        lambda cell, traffic: jax.devices()[:1])
    out = tmp_path / "rows.json"
    rc = control_compare.main(
        ["--workload", CELL, "--seed", "7", "--out", str(out)])
    rows = json.loads(out.read_text())["rows"]
    for name in ("sound", "reference_in_two_batches"):
        assert rows[name]["correct"], (name, rows[name])
    for name in ("bf16", "half_the_rows", "weights_never_moved"):
        assert not rows[name]["correct"], (name, rows[name])
    assert rows["high"]["compared"]["ll_gap_max"]["value"] == 0
    assert rc == 1  # ``high`` read correct here, so not every control failed
    assert capsys.readouterr().out.count('"answers"') == 6


def test_sparse_work_function_counts_stored_elements():
    from chipbench.work import lbfgs, lbfgs_sparse

    config = run.load_cell(CELL)[2]
    flops = lbfgs_sparse.fit_flops(config)
    n_tr = 11314 - 11314 // 5
    passes = 6 * config["estimator"]["max_iter"] + 4
    assert flops == pytest.approx(passes * (1787565 * 0.8 + n_tr) * 20)
    # the dense count of the same fit is some 800 times that
    assert 700 < lbfgs.fit_flops(config) / flops < 900
