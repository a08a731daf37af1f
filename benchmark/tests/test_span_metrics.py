"""The per-layer metrics that read the program's run-lifecycle and wait
spans and counters (PR 25): the two span reducers on hand-made inputs,
the metric files, and `pose_dense_x4` as a cell of the manifest, on four
virtual CPU devices.  Nothing here is a device measurement."""

import json
import os

import pytest

from conftest import BENCH

from reducers import gaps_unnamed, span_uncovered

CONTAINERS = ["run", "run:pipeline"]
NEW = ("client.run_prepare_ms", "client.run_drain_ms",
       "client.run_commit_ms", "evaluate.setup_ms",
       "evaluate.task_wait_ms_per_row", "client.loader_blocked_ms_per_row",
       "client.run_unnamed_pct", "device.idle_unnamed_pct")


def request(t_call, t_done, intervals):
    return {"t_call": t_call, "t_done": t_done, "intervals": intervals}


@pytest.mark.parametrize("intervals,expected", [
    # the containers alone name nothing
    ([("run", 10.0, 12.0), ("run:pipeline", 10.1, 11.9)], 100.0),
    # overlapping children count once; 0.5 s of 2 s stay bare
    ([("run", 10.0, 12.0), ("run:prepare", 10.0, 10.5),
      ("load", 10.4, 11.0), ("run:drain", 11.0, 11.5)], 25.0),
    # an interval that spills past the return is cut there
    ([("evaluate", 9.0, 13.0)], 0.0),
    ([], 100.0)],
    ids=["containers_only", "partly_covered", "covered", "no_intervals"])
def test_span_uncovered(intervals, expected):
    ctx = {"requests": [request(10.0, 12.0, intervals)]}
    assert span_uncovered.read(ctx, CONTAINERS) == pytest.approx(expected)


def test_span_uncovered_weighs_requests_by_their_length():
    ctx = {"requests": [request(0.0, 1.0, [("load", 0.0, 1.0)]),
                        request(1.0, 4.0, [])]}
    assert span_uncovered.read(ctx, CONTAINERS) == pytest.approx(75.0)


def test_span_uncovered_with_nothing_to_read():
    assert span_uncovered.read({"requests": []}, CONTAINERS) is None


def trace(gaps):
    return {"gaps": gaps, "window_lo_ns": 1_000_000_000}


@pytest.mark.parametrize("intervals,expected", [
    # gap A's midpoint is host 100.25, gap B's 101.5
    ([("run:drain", 100.0, 100.5), ("run", 99.0, 103.0)], 200.0 / 3),
    ([("run:drain", 100.0, 100.5), ("evaluate", 101.0, 102.0)], 0.0),
    ([("run", 99.0, 103.0), ("run:pipeline", 99.0, 103.0)], 100.0)],
    ids=["one_named", "both_named", "containers_only"])
def test_gaps_unnamed(intervals, expected):
    # the window opens at host 100.0 = trace 1.0 s; gap A 0.5 s long,
    # gap B 1.0 s long
    ctx = {"trace": trace([[1_000_000_000, 1_500_000_000],
                           [2_000_000_000, 3_000_000_000]]),
           "requests": [request(100.0, 103.0, intervals)]}
    assert gaps_unnamed.read(ctx, CONTAINERS) == pytest.approx(expected)


@pytest.mark.parametrize("ctx", [
    {"trace": None, "requests": [request(0.0, 1.0, [])]},
    {"trace": trace([]), "requests": [request(0.0, 1.0, [])]},
    {"trace": trace([[0, 10]]), "requests": []}],
    ids=["no_device_plane", "no_gaps", "no_requests"])
def test_gaps_unnamed_with_nothing_to_read(ctx):
    assert gaps_unnamed.read(ctx, CONTAINERS) is None


def test_new_metric_files_name_reducers_that_exist(manifest):
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            mdef = json.load(f)
        assert "workloads" not in listed[name], "read in every cell"
        assert mdef["source"] == listed[name]["source"]
        assert os.path.exists(os.path.join(
            BENCH, "reducers", mdef["reducer"] + ".py"))
        if mdef["reducer"] == "counter_ratio":
            assert mdef["source"] == "program_counter"
            assert set(mdef["args"]) <= {"num", "den", "scale"}


def test_pose_dense_x4_is_a_cell_of_the_manifest(run_tiny, manifest):
    cell = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in cell] == ["pose_dense_x4"]
    r = run_tiny("pose_dense_x4", seconds=2.0, trace=True)
    assert r["correct"] and r["failed"] == 0, r["compared"]
    assert r["device"]["count"] == 4
    # everything but the device-trace reader finds its spans and counters
    for name in NEW[:-1] + ("decode.ms_per_frame",):
        assert name in r["metrics"], name
    assert "device.idle_unnamed_pct" not in r["metrics"]
    assert r["metrics"]["client.run_unnamed_pct"]["value"] < 5.0
    # one evaluator set up per chip per run
    assert r["metrics"]["client.run_drain_ms"]["value"] > 0
