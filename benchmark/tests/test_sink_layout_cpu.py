"""PR 39's per-layer metric at 128x96 on the CPU: a traced run of each
cell that lists `save.sink_relaid_rows_pct` reads it, 0 here (the CPU
backend holds every sink batch row-major, so its rows count as `asis`),
and a program without the series (the parent of PR 39) reads nothing
and does not raise.  Counts, not speeds."""

import importlib
import time

import pytest

from conftest import FAKE_DEVICE
from test_span_parts_cpu import overrides

METRIC = "save.sink_relaid_rows_pct"
CELLS = ["blur_dense", "flow_ranges", "hist_dense", "hist_hot"]


def test_the_metric_is_listed_for_its_cells_and_nothing_else_changed(
        manifest):
    last = manifest["per_layer"][-1]
    assert last["name"] == METRIC and last["workloads"] == CELLS
    assert last["moves"] == "frames_per_s" and last["layer"] == "save"


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_reads_the_share_of_rows_laid_out_again(manifest, cell):
    import harness
    spec = harness.find_cell(manifest, cell)
    r = harness.run_cell(manifest, cell, 2 ** 31 + 39, 1.0, True,
                         time.time(),
                         dict(FAKE_DEVICE, count=spec["chips"]),
                         overrides=overrides(manifest, cell))
    assert r["correct"] and r["failed"] == 0, r["compared"]
    assert r["metrics"][METRIC] == {"value": 0.0, "unit": "%"}


def test_a_program_without_the_series_reads_nothing():
    import harness
    mdef = harness.load_json("metrics", METRIC + ".json")
    reader = importlib.import_module("reducers." + mdef["reducer"])
    ctx = {"rows": 512, "counter_delta": lambda s, la=None, nl=None: 0.0}
    assert reader.read(ctx, **mdef["args"]) is None
    counted = {"relaid": 96.0, "asis": 32.0}
    ctx["counter_delta"] = lambda s, la=None, nl=None: \
        counted[la["layout"]] if la else sum(counted.values())
    assert reader.read(ctx, **mdef["args"]) == 75.0
