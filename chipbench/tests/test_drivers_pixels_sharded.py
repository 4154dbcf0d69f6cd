"""The row-sharded dense multiclass search driver and its reference at
a small size on the CPU's four virtual devices: a sound run on the
``tasks`` 1 x ``data`` 4 mesh reads correct; the control (the program's
bfloat16 path) and the planted faults read NOT correct; and the
reference's parts add up to the whole — over one part it IS the blocked
reference, over two and four it agrees with it to the rounding of
float32 sums. The limits here are this size's own (160 rows a test
fold, both solvers float32); the cell's are read on the chips."""

import copy
import json
import time

import jax
import numpy as np
import pytest

from chipbench import datagen_pixels, peaks, run
from chipbench.drivers import search_pixels_sharded as driver

CELL = "search-mnist8m-full-4chip"


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    table = peaks.load()
    monkeypatch.setattr(
        peaks, "load", lambda path=None: dict(table, cpu=table["TPU v5 lite"]))


def small():
    bench, cell, config, traffic = run.load_cell(CELL)
    config = copy.deepcopy(config)
    config["data"].update(n=800, threads=2)
    config["estimator"]["max_iter"] = 40
    config["search"].update(C_logspace=[-3, 0, 5])
    # read at this size on seeds 7, 2**31 + 31 and 12345 (four parts
    # against a four-device mesh): sound 6.6e-6 to 8.6e-6 / 3.6e-5 to
    # 7.4e-5, the bfloat16 path 3.9e-4 to 6.6e-4 / 1.4e-3 to 2.9e-3,
    # half the rows 0.15 to 0.17 / 0.22 to 0.25
    config["compare"] = {"sample": 10, "reference": {"block_rows": 100},
                         "limits": {"ll_gap_median": 5e-5,
                                    "ll_gap_max": 4e-4}}
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
                       jax.devices()[:4], t_start=time.perf_counter())
    json.dumps(out)
    return out


def test_the_cell_is_the_whole_source_on_all_its_chips():
    bench, cell, config, traffic = run.load_cell(CELL)
    assert cell["chips"] == 4 and traffic["devices"] == "all"
    assert config["reduced"] == [] and "published" not in config
    assert config["data"]["n"] == 8_100_000
    assert config["compare"]["batches"] == 3
    base = run.load_cell("search-mnist8m")[2]
    for key in ("search", "estimator"):
        assert config[key] == base[key]
    assert {k: v for k, v in config["data"].items()
            if k not in ("n", "threads")} == {
        k: v for k, v in base["data"].items() if k != "n"}
    # the generator's threads change its time, not its rows
    X, y = datagen_pixels.digit_like_rows(22, 70_000, threads=24)
    X8, y8 = datagen_pixels.digit_like_rows(22, 70_000, threads=8)
    assert np.array_equal(X, X8) and np.array_equal(y, y8)


def test_sharded_search_cell_traced_reads_correct_and_its_metrics(spans):
    out = drive(trace=1)
    assert out["correct"] and out["failed"] == 0, out["compared"]
    assert out["attempted"] % 25 == 0 and out["device"]["count"] == 4
    for name in ("collective_mb_per_program.search",
                 "logits_share_of_lane_pct.search",
                 "round_retries_per_fit.search", "lbfgs_mfu_pct.search",
                 "loss_evals_per_fit.search", "lbfgs_iters_per_fit.search",
                 "lanes_per_round.search", "live_lane_share_pct.search",
                 "place_s_per_fit.search", "refit_s_per_fit.search",
                 "search_host_s_per_fit.search", "window_compiles.search",
                 "round_mem_vs_compiled_pct.search"):
        assert name in out["metrics"], name
    # a CPU's trace has no device plane: nothing to spread
    assert "shard_busy_spread_pct.search" not in out["metrics"]
    assert "round_mem_estimate_pct.search" not in out["metrics"]
    assert out["metrics"]["round_retries_per_fit.search"]["value"] == 0
    assert out["metrics"]["window_compiles.search"]["value"] == 0
    # partial sums of a round's lanes (the loss, X^T r of 785 x 10 a
    # lane), not rows of X or of the logits
    assert 0 < out["metrics"]["collective_mb_per_program.search"][
        "value"] < 10
    # the refit ran on the mesh: a placement of its own under `refit`
    assert out["metrics"]["place_s_per_fit.search"]["value"] > 0


def untouched_weights(state, scores):
    return np.full_like(scores, -np.log(state["config"]["data"]["k"]))


def half_the_rows(state, scores):
    half = dict(state, X=state["X"][::2], y=state["y"][::2])
    return search_fit(half)[2]


def one_answer_altered(state, scores):
    c, f = driver.sample_pairs(state)[0]
    scores = scores.copy()
    scores[c, f] += 5e-3
    return scores


search_fit = driver.fit


@pytest.mark.parametrize("breaker", [untouched_weights, half_the_rows,
                                     one_answer_altered])
def test_sharded_search_faults_read_not_correct(monkeypatch, breaker):
    real = driver.fit

    def fit(state):
        failed, stats, answer = real(state)
        return failed, stats, breaker(state, answer)

    monkeypatch.setattr(driver, "fit", fit)
    out = drive()
    assert out["failed"] == 0 and not out["correct"], out["compared"]


def test_sharded_search_control_reads_not_correct():
    """The program's own bfloat16 path against the float32 reference."""
    bench, cell, config, traffic = small()
    state = driver.setup(config, 7, jax.devices()[:4])
    sound = driver.compare(state, [driver.fit(state)[2]])
    control = driver.compare(state, driver.control_answers(state))
    assert all(c["value"] <= c["limit"] for c in sound), sound
    assert any(c["value"] > c["limit"] for c in control), control


def test_a_program_without_the_capability_ends_the_run_at_once(monkeypatch):
    from skdist_tpu.parallel import backend

    class OldPlan:
        __slots__ = ("init_fn", "shared")

    monkeypatch.setattr(backend, "IterativePlan", OldPlan)
    with pytest.raises(SystemExit, match="data_shards"):
        driver.setup(small()[2], 7, jax.devices()[:4])


@pytest.mark.parametrize("parts", [1, 2, 4])
def test_the_parts_add_up_to_the_blocked_reference(parts):
    """Values, gradients and row losses of ``RowShardedSoftmaxLR`` over
    ``parts`` devices against ``BlockedSoftmaxLR`` over the whole, for
    three fits side by side with their own masks and C: one part gives
    the same digits; two and four the same sums in another order —
    float32 block sums added in float64 on the host where the whole
    adds them in float32 on the device, within 2e-6 of the value and
    2e-5 of the largest gradient entry, the room
    ``test_the_blocked_reference_is_the_plain_one_block_by_block``
    gives blocks against the whole."""
    import jax.numpy as jnp

    from chipbench.reference.softmax_lr_blocked import BlockedSoftmaxLR
    from chipbench.reference.softmax_lr_rowsharded import (
        RowShardedSoftmaxLR, part_bounds,
    )

    assert part_bounds(10, 4) == [(0, 2), (2, 5), (5, 7), (7, 10)]
    assert part_bounds(8_100_000, 4)[1] == (2_025_000, 4_050_000)
    X, y = datagen_pixels.digit_like_rows(5, 800)
    whole = BlockedSoftmaxLR(X, y, 10, block_rows=100)
    cut = RowShardedSoftmaxLR(X, y, 10, jax.devices()[:parts],
                              block_rows=100)
    assert [p._X.shape for p in cut._parts] == [
        (800 // parts // 100, 100, 784)] * parts
    assert [next(iter(p._X.devices())) for p in cut._parts] == list(
        jax.devices()[:parts])
    rng = np.random.RandomState(0)
    W = jnp.asarray(0.05 * rng.normal(size=(3, 785 * 10)), jnp.float32)
    masks = jnp.asarray(rng.rand(3, 800) < 0.7, jnp.float32)
    inv_c = jnp.asarray([10.0, 1.0, 0.01], jnp.float32)
    f, g = (np.asarray(a, np.float64)
            for a in whole._values_and_grads(W, masks, inv_c))
    fc, gc = cut._values_and_grads(W, masks, inv_c)
    v, vc = np.asarray(whole._values(W, masks, inv_c), np.float64), (
        cut._values(W, masks, inv_c))
    rows, rows_c = np.asarray(whole._row_loss(W[1])), cut._row_loss(W[1])
    if parts == 1:
        assert np.array_equal(fc, f) and np.array_equal(gc, g)
        assert np.array_equal(vc, v) and np.array_equal(rows_c, rows)
    else:
        np.testing.assert_allclose(fc, f, rtol=2e-6)
        np.testing.assert_allclose(vc, v, rtol=2e-6)
        np.testing.assert_allclose(gc, g, atol=2e-5 * np.abs(g).max())
        np.testing.assert_allclose(rows_c, rows, rtol=1e-5, atol=1e-6)
    # and through the solver, all the way to two fits' answers: thirty
    # iterations carry a last digit of a sum into the path (4e-5 was
    # read here; PERF.md section 2 has what two sound solvers differ by
    # at the cell's size), one part carries nothing
    folds = [(np.arange(0, 800, 2), np.arange(1, 800, 2))]
    jobs = [(0, 0.01), (0, 1.0)]
    a = whole.fold_scores(folds, jobs, 30, 1e-4)
    b = cut.fold_scores(folds, jobs, 30, 1e-4)
    if parts == 1:
        assert a == b
    else:
        np.testing.assert_allclose(b, a, atol=3e-4)
