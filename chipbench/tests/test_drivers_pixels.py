"""The dense multiclass search driver, its generator and its blocked
reference at a small size on the CPU: a sound run reads correct; the
control (the program's bfloat16 path) and the planted faults read NOT
correct. The limits here are this size's own (200 rows a test fold,
both solvers float32); the cell's are read on the chip."""

import copy
import json
import time

import jax
import numpy as np
import pytest

from chipbench import datagen_pixels, peaks, run
from chipbench.drivers import search_pixels

CELL = "search-mnist8m"


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    table = peaks.load()
    monkeypatch.setattr(
        peaks, "load", lambda path=None: dict(table, cpu=table["TPU v5 lite"]))


def small():
    bench, cell, config, traffic = run.load_cell(CELL)
    config = copy.deepcopy(config)
    config["data"].update(n=600)
    config["estimator"]["max_iter"] = 40
    config["search"].update(C_logspace=[-3, 0, 5])
    # read at this size on seeds 7, 2**31 + 31 and 12345: sound 1.2e-6
    # to 1.7e-6 / 9.3e-5 to 1.2e-4, the bfloat16 path 1.6e-4 to 2.4e-4 /
    # 1.5e-3 to 2.5e-3, half the rows 0.11 to 0.15 / 0.21 to 0.40
    config["compare"] = {"sample": 10, "reference": {"block_rows": 150},
                         "limits": {"ll_gap_median": 2e-5,
                                    "ll_gap_max": 5e-4}}
    return bench, cell, config, traffic


@pytest.fixture
def spans():
    from skdist_tpu.obs import trace as obs_trace

    was = obs_trace.enabled()
    obs_trace.set_enabled(True)
    obs_trace.clear()
    yield
    obs_trace.set_enabled(was)


def drive(trace=0, seed=2 ** 31 + 31):
    bench, cell, config, traffic = small()
    out = run.run_cell(bench, cell, config, traffic, seed, 0.2, trace,
                       jax.devices()[:1], t_start=time.perf_counter())
    json.dumps(out)
    return out


def test_the_generator_keeps_its_contract():
    """Shape, value range, the frame's zero columns, the share of a row
    that is ink, class balance, and the same data from the same seed."""
    X, y = datagen_pixels.digit_like_rows(22, 20_000)
    assert X.shape == (20_000, 784) and X.dtype == np.float32
    assert X.min() == 0.0 and 0.99 < X.max() <= 1.0
    zero = np.flatnonzero(X.max(axis=0) == 0)
    assert len(zero) == datagen_pixels.ZERO_PIXELS == 65
    # the zero columns are the frame's corners, not the middle
    r, c = np.divmod(zero, 28)
    assert np.all(np.hypot(r - 13.5, c - 13.5) > 14)
    assert 0.15 < np.mean(X != 0) < 0.25
    counts = np.bincount(y, minlength=10)
    assert len(counts) == 10 and counts.min() > 0.9 * counts.max()
    # not standardised: columns differ in scale by an order of magnitude
    spread = X.std(axis=0)[X.max(axis=0) > 0]
    assert spread.max() > 8 * spread.min()
    X2, y2 = datagen_pixels.digit_like_rows(22, 20_000, threads=2)
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    X3, _ = datagen_pixels.digit_like_rows(23, 20_000)
    assert not np.array_equal(X, X3)
    with pytest.raises(ValueError, match="28 x 28"):
        datagen_pixels.digit_like_rows(22, 100, d=48)


def test_a_linear_model_reads_the_digits_about_as_well_as_mnist():
    from sklearn.linear_model import LogisticRegression

    X, y = datagen_pixels.digit_like_rows(22, 12_000)
    clf = LogisticRegression(max_iter=60).fit(X[:8000], y[:8000])
    assert 0.80 < clf.score(X[8000:], y[8000:]) < 0.95


def test_the_seed_draws_the_sample_not_the_data():
    bench, cell, config, traffic = small()
    a, b = (search_pixels.setup(config, seed, jax.devices()[:1])
            for seed in (1, 2 ** 31 + 2))
    assert np.array_equal(a["X"], b["X"]) and np.array_equal(a["y"], b["y"])
    pa, pb = search_pixels.sample_pairs(a), search_pixels.sample_pairs(b)
    assert pa != pb and len(pa) == len(pb) == 10
    assert {c for c, _ in pa} == {c for c, _ in pb} == set(range(5))
    # the cell itself compares every pair, in three batches
    full = dict(a, config=run.load_cell(CELL)[2])
    full["Cs"] = list(range(10))
    counts = np.bincount([c for c, _ in search_pixels.sample_pairs(full)])
    assert list(counts) == [5] * 10
    assert full["config"]["compare"]["batches"] == 3


def test_the_blocked_reference_is_the_plain_one_block_by_block():
    """Values, gradients and row losses of ``BlockedSoftmaxLR`` against
    ``SoftmaxLR``'s over the whole matrix at once, for three fits side
    by side with their own masks and C."""
    import jax.numpy as jnp

    from chipbench.reference.softmax_lr import SoftmaxLR
    from chipbench.reference.softmax_lr_blocked import (
        BlockedSoftmaxLR, block_count,
    )

    assert block_count(600, 150) == 4 and block_count(600, 70) == 10
    assert block_count(2_000_000, 125_000) == 16
    X, y = datagen_pixels.digit_like_rows(5, 600)
    plain = SoftmaxLR(X, y, 10)
    blocked = BlockedSoftmaxLR(X, y, 10, block_rows=70)
    rng = np.random.RandomState(0)
    W = jnp.asarray(0.05 * rng.normal(size=(3, 785 * 10)), jnp.float32)
    masks = jnp.asarray(rng.rand(3, 600) < 0.7, jnp.float32)
    inv_c = jnp.asarray([10.0, 1.0, 0.01], jnp.float32)
    f, g = plain._values_and_grads(W, masks, inv_c)
    fb, gb = blocked._values_and_grads(W, masks, inv_c)
    np.testing.assert_allclose(fb, f, rtol=2e-6)
    np.testing.assert_allclose(gb, g, atol=2e-5 * float(jnp.abs(g).max()))
    np.testing.assert_allclose(blocked._values(W, masks, inv_c), f,
                               rtol=2e-6)
    np.testing.assert_allclose(blocked._row_loss(W[1]),
                               plain._row_loss(W[1]), rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="multinomial"):
        BlockedSoftmaxLR(X, y, 2)


def test_pixels_search_cell_traced_reads_correct_and_its_metrics(spans):
    out = drive(trace=1)
    assert out["correct"] and out["failed"] == 0, out["compared"]
    assert out["attempted"] % 25 == 0
    for name in ("logits_share_of_lane_pct.search",
                 "round_retries_per_fit.search", "lbfgs_mfu_pct.search",
                 "loss_evals_per_fit.search", "lbfgs_iters_per_fit.search",
                 "lanes_per_round.search", "live_lane_share_pct.search",
                 "place_s_per_fit.search", "refit_s_per_fit.search",
                 "search_host_s_per_fit.search", "window_compiles.search"):
        assert name in out["metrics"], name
    # no memory counter on a CPU, so no estimate against it
    assert "round_mem_estimate_pct.search" not in out["metrics"]
    assert out["metrics"]["round_retries_per_fit.search"]["value"] == 0
    # at 600 rows a lane is its 7,850 weights and their history; at the
    # cell's 2,000,000 its logits (tests/test_tpu_compile.py)
    assert 0 <= out["metrics"]["logits_share_of_lane_pct.search"][
        "value"] < 50
    assert out["metrics"]["window_compiles.search"]["value"] == 0


def untouched_weights(state, scores):
    return np.full_like(scores, -np.log(state["config"]["data"]["k"]))


def half_the_rows(state, scores):
    half = dict(state, X=state["X"][::2], y=state["y"][::2])
    return search_fit(half)[2]


def one_answer_altered(state, scores):
    c, f = search_pixels.sample_pairs(state)[0]
    scores = scores.copy()
    scores[c, f] += 5e-3
    return scores


search_fit = search_pixels.fit


@pytest.mark.parametrize("breaker", [untouched_weights, half_the_rows,
                                     one_answer_altered])
def test_pixels_search_faults_read_not_correct(monkeypatch, breaker):
    real = search_pixels.fit

    def fit(state):
        failed, stats, answer = real(state)
        return failed, stats, breaker(state, answer)

    monkeypatch.setattr(search_pixels, "fit", fit)
    out = drive()
    assert out["failed"] == 0 and not out["correct"], out["compared"]


def test_pixels_search_control_reads_not_correct():
    """The program's own bfloat16 path against the float32 reference."""
    bench, cell, config, traffic = small()
    state = search_pixels.setup(config, 7, jax.devices()[:1])
    sound = search_pixels.compare(state, [search_pixels.fit(state)[2]])
    control = search_pixels.compare(
        state, search_pixels.control_answers(state))
    assert all(c["value"] <= c["limit"] for c in sound), sound
    assert any(c["value"] > c["limit"] for c in control), control


def test_the_dense_work_function_counts_ten_columns():
    from chipbench.work import lbfgs

    config = run.load_cell(CELL)[2]
    n_tr = config["data"]["n"] - config["data"]["n"] // 5
    assert lbfgs.fit_flops(config) == pytest.approx(
        (6 * 100 + 4) * n_tr * 784 * 10)
