"""PR 36's per-layer metrics at 128x96 on the CPU: a traced run of
every cell reads each of the new counter metrics that BENCHMARK.json
lists for it, above 0 where the part it reads is worked there; the
metrics read from a device trace return nothing here (no device plane
on the CPU) and the line leaves them out; a program without the new
series (the parent of PR 36) reads nothing and does not raise.  Counts,
not speeds."""

import time

import pytest

from conftest import FAKE_DEVICE, TINY as BASE
from test_flow_cell_cpu import TINY as FLOW
from test_frame_cells_cpu import TINY as FRAMES
from test_new_cells_cpu import SLICED, TINY as NEW

FIRST = "client.prepare_jobs_ms"  # the first entry PR 36 appended
# read from the device planes: nothing on the CPU
TRACE_ONLY = {"staging.cache_device_ms_per_row",
              "evaluate.columnbatch_device_ms_per_row",
              "device.unscoped_pct", "device.idle_coarse_pct"}
# may read 0: nothing strayed, or every second had a name
MAY_BE_ZERO = {"client.stray_compile_ms_per_run",
               "client.load_unnamed_ms_per_row",
               "evaluate.unnamed_ms_per_row"}
# at this size no task of the cell is longer than a work packet, so
# none streams and no chunk is assembled on the host
NOT_AT_THIS_SIZE = {("hist_gather_hot", "staging.assemble_ms_per_row")}
CELLS = ["hist_dense", "hist_hot", "pose_dense", "pose_dense_x4",
         "hist_stride", "shot_dense", "blur_dense", "hist_gather_hot",
         "hist_sliced", "flow_ranges"]


def new_metrics(manifest, cell):
    names = [m["name"] for m in manifest["per_layer"]]
    return [m["name"] for m in manifest["per_layer"][names.index(FIRST):]
            if cell in m.get("workloads", [cell])]


def overrides(manifest, cell):
    import harness
    traffic = harness.find_cell(manifest, cell)["traffic"]
    if cell == "flow_ranges":
        return FLOW
    over = {**BASE, **FRAMES, **NEW}[traffic]
    return harness.merge(over, SLICED) if cell == "hist_sliced" else over


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_reads_the_new_metrics_listed_for_the_cell(
        manifest, cell):
    import harness
    spec = harness.find_cell(manifest, cell)
    r = harness.run_cell(manifest, cell, 2 ** 31 + 36, 1.0, True,
                         time.time(),
                         dict(FAKE_DEVICE, count=spec["chips"]),
                         overrides=overrides(manifest, cell))
    assert r["correct"] and r["failed"] == 0, r["compared"]
    listed = new_metrics(manifest, cell)
    assert len(listed) >= 14, listed
    for name in listed:
        if name in TRACE_ONLY:
            assert name not in r["metrics"], name
            continue
        if (cell, name) in NOT_AT_THIS_SIZE:
            continue
        assert name in r["metrics"], name
        value = r["metrics"][name]["value"]
        assert value >= -1e-3 if name in MAY_BE_ZERO else value > 0, \
            (name, value)
    if cell == "flow_ranges":
        got = {k: v["value"] for k, v in r["metrics"].items()}
        parts = sum(got[f"save.raw_{p}_ms_per_row"]
                    for p in ("pickle", "build", "backend_write"))
        assert parts == pytest.approx(got["save.raw_write_ms_per_row"],
                                      rel=0.05)


def test_a_program_without_the_series_reads_nothing(manifest):
    """The parent's counters: every new reader returns nothing, and
    raises nothing, where the program has no such series."""
    import importlib

    import harness
    ctx = {"rows": 512, "requests": [], "trace": None, "cfg": {},
           "peaks": {}, "memory_stats": [],
           "counter_delta": lambda s, la=None, nl=None:
               7.0 if s in ("scanner_tpu_stage_seconds_total",
                            "scanner_tpu_runs_total",
                            "scanner_tpu_decode_seconds_total",
                            "scanner_tpu_chunk_wait_seconds_total",
                            "scanner_tpu_raw_frame_seconds_total") else 0.0}
    names = [m["name"] for m in manifest["per_layer"]]
    for m in manifest["per_layer"][names.index(FIRST):]:
        mdef = harness.load_json("metrics", m["name"] + ".json")
        reader = importlib.import_module("reducers." + mdef["reducer"])
        assert reader.read(ctx, **mdef.get("args", {})) is None, m["name"]


def test_self_time_takes_absent_parts_as_nothing():
    from reducers import counter_less
    counts = {"whole": 10.0, "a": 3.0, "b": 0.0, "w": 1.0}
    ctx = {"rows": 4, "counter_delta":
           lambda s, la=None, nl=None: counts.get(s, 0.0)}
    spec = {k: {"series": k} for k in counts}
    assert counter_less.read(ctx, spec["whole"], spec["w"], "rows",
                             parts=[spec["a"], spec["b"]]) == 1.75
    assert counter_less.read(ctx, spec["b"], spec["w"], "rows") == 0.0
    # the witness counted nothing: the program has no such parts
    assert counter_less.read(ctx, spec["whole"], spec["b"], "rows",
                             parts=[spec["a"]]) is None


def test_unscoped_share_of_a_recorded_trace():
    """The chip's own trace of `hist_dense` (tests/data, recorded at
    PR 33): Histogram and the conversion name most of its busy time;
    the frame cache's programs, unscoped then, are the rest."""
    import os

    import trace_reduce
    from conftest import HERE
    from reducers import scopes_uncovered
    reduced = trace_reduce.reduce_trace(trace_reduce.load(os.path.join(
        HERE, "data", "hist_dense_v5e.xplane.pb")))
    ctx = {"trace": reduced}
    both = scopes_uncovered.read(ctx, ["Histogram", "yuv420_to_rgb"])
    one = scopes_uncovered.read(ctx, ["Histogram"])
    assert 0.0 <= both < one < 100.0
    assert scopes_uncovered.read({"trace": None}, ["Histogram"]) is None
