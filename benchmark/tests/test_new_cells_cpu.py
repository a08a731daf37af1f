"""The cells of PR 27, `hist_stride` and `shot_dense`, at 128x96 on the
CPU (conftest.py's `TINY` is keyed by traffic name and knows neither, so
the cuts are here): the references agree with the program through
`Client.run`, and a shifted row, the lower-precision control and a
comparison that shrank come out as not correct."""

import time

import numpy as np
import pytest

from conftest import FAKE_DEVICE, SMALL

TINY = {
    "dense_stride30": {"config": SMALL,
                       "traffic": {"tables": 4, "resident_tables": 2,
                                   "fill_bulk_tables": 2, "streams": 2}},
    # a run is rows 0-39 or 40-63 of a 64-row table: it crosses 16-row
    # packets off their edges, as the cell's runs of 96 do
    "dense_windows": {"config": SMALL,
                      "traffic": {"tables": 4, "resident_tables": 2,
                                  "fill_bulk_tables": 2, "streams": 2,
                                  "check": {"streams": 3, "rows": 40}}},
}
NEW_METRICS = ("decode.codec_frames_per_row", "evaluate.op_rows_per_row",
               "evaluate.window_ms_per_row", "evaluate.host_op_ms_per_row")


@pytest.fixture()
def run_new(manifest):
    import harness

    def go(cell, seed=2 ** 31 + 11, seconds=1.0, trace=False, over=None):
        spec = harness.find_cell(manifest, cell)
        return harness.run_cell(
            manifest, cell, seed, seconds, trace, time.time(),
            dict(FAKE_DEVICE), overrides=harness.merge(TINY[spec["traffic"]],
                                                       over))
    return go


@pytest.mark.parametrize("cell", ["hist_stride", "shot_dense"])
def test_reference_agrees_with_the_program(run_new, cell):
    r = run_new(cell)
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"frames_per_s", "setup_s"}
    if cell == "shot_dense":
        assert r["compared"]["delta_rows_differ"]["value"] == 0
        # of three runs, those that do not start at row 0 leave one row
        assert r["compared"]["delta_uncompared_share"]["value"] <= 3 / 72


def test_stride_decodes_more_than_it_delivers(run_new):
    """Rows 0, 30 and 60 of a 64-frame clip with a keyframe every 32:
    the next keyframe lies within `decode_through` of each row, so the
    plan is one run, frames 0-60, for three rows."""
    r = run_new("hist_stride", trace=True)
    assert r["correct"], r["compared"]
    assert r["metrics"]["decode.frames_per_row"]["value"] == 1.0
    assert r["metrics"]["decode.codec_frames_per_row"]["value"] == 61 / 3
    assert not set(NEW_METRICS[1:]) & set(r["metrics"])


def test_shot_traced_run_reports_every_new_metric(run_new):
    r = run_new("shot_dense", trace=True)
    assert r["correct"], r["compared"]
    assert set(NEW_METRICS) <= set(r["metrics"])
    assert r["metrics"]["evaluate.op_rows_per_row"]["value"] > 1.0
    # whole GOPs: the codec decodes what it delivers (the shrunk cache
    # is not full, so some rows are hits and neither count reaches 1)
    assert r["metrics"]["decode.codec_frames_per_row"]["value"] \
        == r["metrics"]["decode.frames_per_row"]["value"] > 0
    assert r["metrics"]["evaluate.host_op_ms_per_row"]["value"] > 0


def test_a_row_shifted_by_one_is_not_correct(run_new, monkeypatch):
    """A sink that commits every distance one row late."""
    import harness
    real = harness.Cell.load

    def shifted(self, rec, j, rows):
        rows = list(rows)
        return real(self, rec, j, [max(0, rows[0] - 1)] + rows[:-1])

    monkeypatch.setattr(harness.Cell, "load", shifted)
    r = run_new("shot_dense")
    assert not r["correct"]
    assert r["compared"]["delta_rows_differ"]["value"] > 0
    assert r["compared"]["rows_missing"]["value"] == 0


def test_a_comparison_of_single_rows_is_not_correct(run_new):
    """`check.rows` 1: no sampled row has its predecessor beside it."""
    r = run_new("shot_dense", over={"traffic": {"check": {"rows": 1}}})
    assert not r["correct"]
    assert r["compared"]["delta_rows_differ"]["value"] == 0
    got = r["compared"]["delta_uncompared_share"]
    assert got["value"] >= 2 / 3 > got["limit"]


def _wire_sample(rows, h=96, w=128):
    import clipgen
    from reference import wire
    src = clipgen.ClipSource(5, h, w)
    rng = np.random.default_rng(0)
    # the clip's own luma (it carries the barcode) under noise for
    # chroma, so that the precision of the conversion matters
    return [np.concatenate([
        src.frame(i)[..., 1].ravel(),
        rng.integers(0, 256, wire.wire_bytes(h, w) - h * w, dtype=np.uint8)])
        for i in rows]


def test_delta_control_is_not_correct():
    from reference import Histogram, HistogramDelta as R
    cfg = {"video": {"height": 96, "width": 128}}
    rows = list(range(0, 6)) + list(range(40, 46))
    flat = _wire_sample(rows)
    hists = [Histogram.expected(f, 96, 128) for f in flat]
    exact = R.stream_deltas(hists)
    same = R.compare(cfg, flat, exact)
    # row 40 stands behind row 5: its predecessor is not in the sample
    assert same == {"delta_rows_differ": 0,
                    "delta_uncompared_share": 1 / 12}
    ctl = R.compare(cfg, flat, exact, control=R.CONTROL)
    assert ctl["delta_rows_differ"] > R.LIMITS["delta_rows_differ"]
    # an answer that is off by one count, and one that is no scalar
    off = list(exact)
    off[3] += 1.0
    assert R.compare(cfg, flat, off)["delta_rows_differ"] == 1
    off[4] = np.zeros(2)
    assert R.compare(cfg, flat, off)["delta_rows_differ"] == 2
