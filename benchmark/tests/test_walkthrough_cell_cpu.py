"""The cell of PR 40, `walkthrough_dense`, at 384x216 -> 128x96 on the
CPU (the cell's own scales, 3 along a row and 2.25 down a column): the
whole run reads `correct` with the committed items the reference's own
round trip, the traced run reads every new metric that a CPU run can,
and each control of the reference comes out as not correct by its own
number alone through `walkthrough_controls_on_chip.py`.  Counts, not
speeds.

The floor is this size's (a 128x96 frame of this content stands
39.1-39.7 dB from the reference after the stated encode, without
antialiasing 33.9-35.5; at the cell's size 42.3-42.5 and 37.6-38.7:
PERF.md sec. 2); the other limits are the cell's own.

At this size `PerfParams.estimate()` would make one task of a stream
where the configuration states items of 32 rows, so the rehearsal pins
the stated cut, tasks of 32 rows in packets of 16: what `estimate()`
gives the 1080p video."""

import time

import pytest

from conftest import FAKE_DEVICE

OPS = [{"op": "Resize", "stream_args": {"width": 128, "height": 96}},
       {"op": "Grayscale"},
       {"op": "CloneChannels", "args": {"replications": 3}}]
TINY = {"config": {"video": {"width": 384, "height": 216},
                   "output": {"width": 128, "height": 96,
                              "psnr_floor_db": 37.4},
                   "graph": {"ops": OPS},
                   "client": {"perf": {"frame_cache_mb": 32}}},
        "traffic": {"tables": 4, "resident_tables": 2,
                    "fill_bulk_tables": 2, "streams": 2}}
NEW_METRICS = ("kernels.walkthrough_roofline",
               "kernels.chain_device_ms_per_row", "evaluate.fused_rows_pct",
               "evaluate.handoff_ms_per_row", "evaluate.handoff_mb_per_row",
               "evaluate.python_op_ms_per_row")
FAILS_BY = {"bf16": "bf16_pattern_share", "nearest": "psnr_under_floor_db",
            "no_gray": "gray_channel_spread"}


@pytest.fixture(autouse=True)
def stated_cut(monkeypatch):
    from scanner_tpu import PerfParams
    monkeypatch.setattr(
        PerfParams, "estimate",
        classmethod(lambda cls, **kw: cls.manual(16, 32, **kw)))


@pytest.fixture()
def run_walkthrough(manifest):
    import harness

    def go(seed, seconds=1.0, trace=False):
        return harness.run_cell(manifest, "walkthrough_dense", seed, seconds,
                                trace, time.time(), dict(FAKE_DEVICE),
                                overrides=TINY)
    return go


@pytest.mark.parametrize("seed", [2 ** 31 + 41, 8])
def test_walkthrough_dense_commits_the_references_own_round_trip(
        run_walkthrough, seed):
    r = run_walkthrough(seed)
    assert r["correct"] and r["failed"] == 0, r["compared"]
    compared = {k: v["value"] for k, v in r["compared"].items()}
    assert compared.pop("psnr_under_floor_db") < -1.0
    # XLA's CPU backend puts a handful of pixels one level off (a
    # contracted multiply-add), and x264 is chaotic: at this size an
    # item's mean then moves by up to 0.012 either way (0.0 where no
    # pixel moved); under the limit, which `correct` has held
    assert abs(compared.pop("psnr_deficit_db")) < 0.015
    assert abs(compared.pop("bf16_pattern_share")) < 0.015
    assert set(compared.values()) == {0}
    assert set(r["metrics"]) == {"frames_per_s", "setup_s"}


def test_the_traced_run_reads_the_new_metrics(run_walkthrough, manifest):
    import harness
    r = run_walkthrough(2 ** 31 + 42, trace=True)
    assert r["correct"], r["compared"]
    got = {k: v["value"] for k, v in r["metrics"].items()}
    assert got["evaluate.fused_rows_pct"] == 100.0
    assert got["evaluate.reuse_pct"] == 100.0
    assert got["evaluate.pad_rows_per_row"] == 0.0
    # a 128x96x3 frame a row, handed over once
    assert got["evaluate.handoff_mb_per_row"] == pytest.approx(0.036864)
    assert got["evaluate.handoff_ms_per_row"] > 0
    assert got["evaluate.python_op_ms_per_row"] > 0
    assert got["decode.frames_per_row"] == 1.0
    assert 2.0 <= got["decode.codec_frames_per_row"] < 2.7
    # the two that read the device trace have nothing to read on a CPU;
    # their files load and name what the harness has
    for name in NEW_METRICS:
        mdef = harness.load_json("metrics", name + ".json")
        entry, = [m for m in manifest["per_layer"] if m["name"] == name]
        assert entry["workloads"] == ["walkthrough_dense"]
        assert {k: mdef[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} \
            == {k: entry[k] for k in ("unit", "better", "source", "layer",
                                      "moves")}
    import importlib
    work = importlib.import_module("work.walkthrough_chain_bytes").work
    assert work({"video": {"height": 1080, "width": 1920},
                 "output": {"height": 480, "width": 640}}, 2) \
        == {"bytes": 2 * (3110400 + 921600)}


@pytest.mark.parametrize("seed", [5, 7])
def test_each_control_is_not_correct_by_its_own_number_alone(manifest, seed):
    import walkthrough_controls_on_chip
    from reference import Walkthrough as R
    recs = walkthrough_controls_on_chip.controls(manifest, seed,
                                                 overrides=TINY)
    assert [rec["control"] for rec in recs] == list(R.CONTROLS) + [None]
    for rec in recs[:-1]:
        assert rec["not_correct"], rec
        assert rec["over"] == [FAILS_BY[rec["control"]]], rec
    # the reference's own round trip in the program's place
    assert not recs[-1]["not_correct"], recs[-1]
    assert recs[-1]["values"]["psnr_deficit_db"] == 0.0
