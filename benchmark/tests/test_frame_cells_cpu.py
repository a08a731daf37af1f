"""The cells of PR 31, `blur_dense` and `hist_gather_hot`, at 128x96 on
the CPU (conftest.py's `TINY` is keyed by traffic name and knows
neither, so the cuts are here): the references agree with the program
through `Client.run`, the traced run reads every metric of the save
stage, and the three controls, a column committed one frame late and a
frame of the wrong size come out as not correct.  Counts, not speeds."""

import time

import numpy as np
import pytest

from conftest import FAKE_DEVICE, SMALL
from harness import merge

TINY = {
    # at this size PerfParams.estimate() makes one task, so one item, of
    # a 64-row table (its 512-row packet holds it), and a run of the
    # cell's 48 rows would hold no whole item: a run is the table.  The
    # floor is this clip's own (the stated encode reads 34.6 dB here,
    # crf 26 31.4), as the configuration's is the 1080p clip's
    "dense_frames": {"config": merge(SMALL, {"output": {
                         "item_rows": 512, "psnr_floor_db": 33.0}}),
                     "traffic": {"tables": 4, "resident_tables": 2,
                                 "fill_bulk_tables": 2, "streams": 2,
                                 "check": {"rows": 64}}},
    # 16 rows over 46 source rows and 8 over 50: two 32-frame pages each
    "hot_gather": {"config": SMALL,
                   "traffic": {"tables": 2, "fill_bulk_tables": 2,
                               "shapes": [{"sampler": "Gather", "count": 16,
                                           "stride": 3},
                                          {"sampler": "Gather", "count": 8,
                                           "stride": 7}]}},
}
SAVE_METRICS = ("save.encode_ms_per_frame", "save.fetch_ms_per_row",
                "save.d2h_mb_per_row", "save.write_ms_per_row",
                "save.encoded_kb_per_row", "save.queue_wait_ms_per_row")


@pytest.fixture()
def run_frames(manifest):
    import harness

    def go(cell, seed=2 ** 31 + 13, seconds=1.0, trace=False, over=None):
        spec = harness.find_cell(manifest, cell)
        return harness.run_cell(
            manifest, cell, seed, seconds, trace, time.time(),
            dict(FAKE_DEVICE), overrides=harness.merge(TINY[spec["traffic"]],
                                                       over))
    return go


@pytest.fixture(scope="module")
def blur_sample(manifest):
    """One untraced run of `blur_dense`; what the harness handed the
    reference (configuration, wire frames, committed frames) is kept, so
    that the controls and the planted faults are compared as a run
    compares them without a run each."""
    import harness
    from reference import Blur
    kept = {}
    real = Blur.compare

    def keeping(cfg, wires, outputs, **kw):
        kept.update(cfg=cfg, wires=list(wires), outputs=list(outputs))
        return real(cfg, wires, outputs, **kw)

    Blur.compare = keeping
    try:
        kept["result"] = harness.run_cell(
            manifest, "blur_dense", 2 ** 31 + 13, 1.0, False, time.time(),
            dict(FAKE_DEVICE), overrides=TINY["dense_frames"])
    finally:
        Blur.compare = real
    return kept


def not_correct(values):
    from reference import Blur
    return [k for k, limit in Blur.LIMITS.items() if values[k] > limit]


def test_blur_reference_agrees_with_the_program(blur_sample):
    r = blur_sample["result"]
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"frames_per_s", "setup_s"}
    assert r["compared"]["frame_shape_errors"]["value"] == 0
    assert r["compared"]["out_frame_id_errors"]["value"] == 0
    # whole items, a deterministic codec: the reference's own round trip
    assert abs(r["compared"]["psnr_deficit_db"]["value"]) < 0.03
    assert r["compared"]["psnr_under_floor_db"]["value"] < -1.0
    assert r["compared"]["blur_response_missing"]["value"] < 0.0
    assert len(blur_sample["outputs"]) == 3 * 64
    assert blur_sample["outputs"][0].shape == (96, 128, 3)


def test_gather_reference_agrees_with_the_program(run_frames):
    r = run_frames("hist_gather_hot")
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"frames_per_s", "job_p95_s", "setup_s"}
    assert 0 < r["metrics"]["job_p95_s"]["value"] < 1e29


def test_gather_traced_run_reads_the_cache(run_frames):
    """Under conftest.py's four virtual devices a query runs on whichever
    instance is free and finds the pages of its own device only, so the
    share is no 100 here; it is reported, and rows were served from
    pages that set-up's contiguous scan left."""
    r = run_frames("hist_gather_hot", trace=True)
    assert r["correct"], r["compared"]
    assert 0 < r["metrics"]["staging.cache_hit_pct"]["value"] <= 100.0
    assert r["metrics"]["decode.frames_per_row"]["value"] < 1.0
    assert not set(SAVE_METRICS) & set(r["metrics"])


def test_blur_traced_run_reports_the_save_stage(run_frames):
    r = run_frames("blur_dense", trace=True)
    assert r["correct"], r["compared"]
    for name in SAVE_METRICS + ("decode.ms_per_frame",):
        assert r["metrics"][name]["value"] > 0, name
    assert r["metrics"]["save.d2h_mb_per_row"]["value"] \
        == pytest.approx(96 * 128 * 3 / 1e6)
    # an evaluator that never found the save queue full waited next to
    # nothing for it; the kept evaluators ran every request
    assert 0 <= r["metrics"]["evaluate.save_wait_pct"]["value"] < 50
    assert r["metrics"]["evaluate.reuse_pct"]["value"] == 100.0


@pytest.mark.parametrize("control,fails_by", [
    ("bf16", ["psnr_deficit_db"]),
    ("crf26", ["psnr_under_floor_db", "psnr_deficit_db"]),
    ("no_blur", ["blur_response_missing"])])
def test_blur_controls_are_not_correct(blur_sample, control, fails_by):
    from reference import Blur
    s = blur_sample
    assert Blur.CONTROL == "bf16" and Blur.CONTROLS[0] == Blur.CONTROL
    values = Blur.compare(s["cfg"], s["wires"], [None] * len(s["wires"]),
                          control=control)
    # the lower precision fails by the exact round trip alone, the
    # coarser quantiser by the floor too, the missing filter by its own
    assert set(fails_by) <= set(not_correct(values)), values
    assert control == "no_blur" or not_correct(values) == fails_by, values


def test_a_column_one_frame_late_is_not_correct(blur_sample):
    from reference import Blur
    s = blur_sample
    late = s["outputs"][:1] + s["outputs"][:-1]
    values = Blur.compare(s["cfg"], s["wires"], late)
    assert values["out_frame_id_errors"] >= len(late) - 3, values
    assert "out_frame_id_errors" in not_correct(values)


def test_a_frame_of_the_wrong_size_is_not_correct(blur_sample):
    from reference import Blur
    s = blur_sample
    outputs = list(s["outputs"])
    outputs[2] = outputs[2][:-2]
    outputs[5] = outputs[5].astype(np.float32)
    values = Blur.compare(s["cfg"], s["wires"], outputs)
    assert values["frame_shape_errors"] == 2
    assert values["out_frame_id_errors"] == 0
    assert not_correct(values) == ["frame_shape_errors"]


def test_lossless_column_reads_a_negative_deficit(blur_sample):
    """A column that lost nothing stands nearer to `expected` than the
    reference's own round trip: the deficit is a distance under it."""
    from reference import Blur
    s = blur_sample
    h, w = s["cfg"]["video"]["height"], s["cfg"]["video"]["width"]
    exact = [Blur.expected(f, h, w) for f in s["wires"]]
    values = Blur.compare(s["cfg"], s["wires"], exact)
    assert values["psnr_deficit_db"] < 0 and not not_correct(values)
