"""The reader of ``lanes_per_round.search`` on hand-built windows."""

import pytest

from chipbench.readers import lanes_per_round


def _ctx(*stats):
    return {"fits": [{"stats": s, "units": 4, "failed": 0} for s in stats]}


@pytest.mark.parametrize("stats, want", [
    # eight slices of one round of 50
    (({"lane_slots": 400, "rounds": 8},), 50.0),
    # 54 rounds of 7, and the finalize pass's rounds left out
    (({"lane_slots": 378, "rounds": 54,
       "finalize": {"rounds": 8, "dispatch_s": 0.1}},), 7.0),
    # summed over the window's fits, not averaged fit by fit
    (({"lane_slots": 400, "rounds": 8}, {"lane_slots": 70, "rounds": 10},
      None), 470 / 18),
])
def test_lanes_a_round(stats, want):
    assert lanes_per_round.read(_ctx(*stats)) == want


@pytest.mark.parametrize("stats", [
    {"rounds": 3, "dispatch_s": 0.5},            # a program without it
    {"lane_slots": None, "rounds": 3},           # no count_keys
    {"lane_slots": 0, "rounds": 0},              # nothing was dispatched
    None,                                        # the fit raised
])
def test_nothing_to_read(stats):
    assert lanes_per_round.read(_ctx(stats)) is None
