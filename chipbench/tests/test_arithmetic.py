"""Trace reduction, window arithmetic, work functions, peaks."""

import pytest

from chipbench import peaks, trace_reduce, window
from chipbench.work import lbfgs

MS = 1e6  # ns


def hand_trace():
    ops = [
        ("%while.1 = (f32[4]) while(...)", 0 * MS, 40 * MS),  # encloses
        ("%fusion.1 = f32[4] fusion(...)", 0 * MS, 10 * MS),
        ("%fusion.2 = f32[4] fusion(...)", 10 * MS, 15 * MS),
        ("%fusion.1 = f32[4] fusion(...)", 30 * MS, 10 * MS),
        ("%copy.3 = f32[4] copy(...)", 60 * MS, 10 * MS),
        ("%copy.3 = f32[4] copy(...)", 90 * MS, 10 * MS),
    ]
    host = [
        ("round_dispatch", 38 * MS, 24 * MS),   # covers the 40-60 gap
        ("round_gather", 69 * MS, 2 * MS),      # a sliver of the 70-90 gap
        ("not_a_span", 70 * MS, 20 * MS),
    ]
    return [
        ("/device:TPU:0", [("Steps", [("step", 0, 100 * MS)]),
                           ("XLA Ops", ops)]),
        ("/device:TPU:1", [("XLA Ops", [
            ("%fusion.1 = f32[4]{0} fusion(...)", 0, 25 * MS)])]),
        ("/host:CPU", [("python", host)]),
    ]


def test_union_merges_nested_and_overlapping():
    assert trace_reduce.union([(0, 40), (0, 10), (5, 20), (60, 70)]) == [
        (0, 40), (60, 70)]
    assert trace_reduce.covered([(0, 10), (5, 20), (30, 31)]) == 21
    assert trace_reduce.gaps([(10, 20), (15, 30)], 0, 50) == [
        (0, 10), (30, 50)]


def test_reduce_busy_idle_and_gap_attribution():
    out = trace_reduce.reduce(hand_trace(), ("round_dispatch",
                                             "round_gather"))
    assert out["window_s"] == pytest.approx(0.100)
    assert out["busy_s_per_device"]["/device:TPU:0"] == pytest.approx(0.060)
    assert out["busy_s_per_device"]["/device:TPU:1"] == pytest.approx(0.025)
    assert out["busy_s"] == pytest.approx(0.0425)
    gaps = dict(out["idle_gaps"])
    assert gaps["round_dispatch"] == pytest.approx(0.020)
    # the 70-90 ms gap: round_gather covers 1 ms of it, nothing else named
    assert gaps["round_gather"] == pytest.approx(0.020)
    assert sum(gaps.values()) == pytest.approx(0.040)
    ops = dict(out["device_ops"])
    # self time: the while is charged only what its body leaves open
    assert ops["fusion.1 f32[4]"] == pytest.approx(0.010 + 0.010 + 0.025)
    assert ops["fusion.2 f32[4]"] == pytest.approx(0.015)
    assert ops["while.1 (f32[4])"] == pytest.approx(0.040 - 0.035)
    assert ops["copy.3 f32[4]"] == pytest.approx(0.020)


def test_reduce_without_device_operations_is_nothing():
    assert trace_reduce.reduce([("/host:CPU", [("python", [
        ("round_dispatch", 0, MS)])])], ("round_dispatch",)) is None


def fake_clock(durations):
    """A clock that advances by the next duration at every fit."""
    now = [0.0]

    def clock():
        return now[0]

    def fit():
        now[0] += durations.pop(0)
        return {"units": 10, "failed": 0}

    return clock, fit


def test_window_rate_is_units_over_elapsed_to_last_completion():
    clock, fit = fake_clock([4.0, 4.0, 4.0, 4.0])
    fits, elapsed = window.run_window(fit, 10.0, clock)
    assert len(fits) == 3 and elapsed == 12.0  # third starts at 8 < 10
    assert window.rate(fits, elapsed) == pytest.approx(30 / 12.0)


def test_a_stall_inside_the_window_lowers_the_rate():
    clock, fit = fake_clock([4.0, 9.0, 4.0])
    fits, elapsed = window.run_window(fit, 10.0, clock)
    assert len(fits) == 2 and elapsed == 13.0
    assert window.rate(fits, elapsed) == pytest.approx(20 / 13.0)


def test_failed_units_do_not_count_and_one_fit_always_runs():
    fits, elapsed = window.run_window(
        lambda: {"units": 10, "failed": 10}, 0.0, 
        iter([0.0, 0.0, 5.0, 5.0]).__next__)
    assert len(fits) == 1 and window.rate(fits, elapsed) == 0.0


def test_work_functions_at_the_cells_shapes():
    # 320,000 training rows of 400,000 in 5 folds; 100 iterations; two
    # classes are one column of weights
    assert lbfgs.lbfgs_fit_flops(320000, 2000, 1, 100) == (
        604 * 320000 * 2000)
    config = {"data": {"n": 400000, "d": 2000, "k": 2},
              "search": {"cv": 5}, "estimator": {"max_iter": 100}}
    assert lbfgs.fit_flops(config) == pytest.approx(3.8656e11, rel=1e-9)
    config["data"]["k"] = 20
    assert lbfgs.fit_flops(config) == pytest.approx(20 * 3.8656e11, rel=1e-9)


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    assert peaks.peak("TPU v5 lite", "bf16_flops") == 197e12
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(ValueError, match="no published"):
        peaks.peak("cpu", "bf16_flops")
