"""The reduction from a trace to busy time, idle gaps and top
operations: on hand-made intervals, and on a trace recorded on the chip
(tests/data, a 6 s window of hist_dense on one v5e chip)."""

import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "hist_dense_v5e.xplane.pb")
S = 1_000_000_000


def test_union_gaps_and_top_ops():
    loaded = {
        "devices": {
            "/device:TPU:0": [("a", 1 * S, 3 * S), ("b", 2 * S, 4 * S),
                              ("a", 6 * S, 7 * S), ("c", 11 * S, 13 * S)],
            "/device:TPU:1": [("a", 0, 2 * S)],
        },
        # each device: the mark just before the window and just after
        "marks": {"/device:TPU:0": [(-5, 0), (10 * S, 10 * S + 5)],
                  "/device:TPU:1": [(-9, 0), (10 * S, 10 * S + 9)]},
    }
    r = tr.reduce_trace(loaded)
    assert r["window_s"] == 10.0
    # overlap counted once, the event past the window left out
    assert r["per_device"] == {"/device:TPU:0": 4.0, "/device:TPU:1": 2.0}
    assert r["busy_s"] == 3.0
    assert r["device_ops"][0] == ["a", 5.0]
    assert dict(map(tuple, r["device_ops"])) == {"a": 5.0, "b": 2.0}
    # the busiest device's gaps, longest first
    assert r["gaps"] == [[7 * S, 10 * S], [4 * S, 6 * S], [0, 1 * S]]
    host = [("evaluate", 100.0, 100.9), ("evaluate:chunk_wait", 104.5, 105.5),
            ("load", 104.0, 109.0)]
    assert tr.label_gaps(r["gaps"], 0, 100.0, host) == \
        [["load", 3.0], ["evaluate:chunk_wait", 2.0], ["evaluate", 1.0]]


def test_no_device_plane_gives_nothing():
    assert tr.reduce_trace({"devices": {}, "marks": {}}) is None
    assert tr.reduce_trace({"devices": {"/device:TPU:0": []},
                            "marks": {}}) is None


def test_a_window_that_cannot_be_placed_is_an_error():
    with pytest.raises(ValueError, match="window mark"):
        tr.reduce_trace({"devices": {"/device:TPU:0": [("a", 0, S)]},
                         "marks": {"/device:TPU:0": [(0, 5)]}})


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_chip_trace():
    loaded = tr.load(DATA)
    assert list(loaded["devices"]) == ["/device:TPU:0"]
    marks = loaded["marks"]["/device:TPU:0"]
    assert len(marks) == 2
    r = tr.reduce_trace(loaded)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["window_s"] == pytest.approx((marks[1][0] - marks[0][1]) / 1e9)
    assert len(r["device_ops"]) <= 10 and len(r["gaps"]) <= 10
    assert all(s > 0 for _, s in r["device_ops"])
    # no operation can have run longer than the device was busy
    assert r["device_ops"][0][1] <= r["busy_s"] + 1e-9
