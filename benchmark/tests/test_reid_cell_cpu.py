"""The cell `reid_dense` on the CPU at 128x96 frames, networks
of width 8, crops of 32x16 and embeddings of 16: the whole run reads
`correct`, the traced run reads every new metric that a CPU run can, the
work functions count the cell's own shapes, the reducer that reads a scope
once counts a loop's body and not the loop, and each control of the
reference comes out as not correct through `reid_controls_on_chip.py`.
Counts, not speeds.

The comparison's tie and the limits of box and vector are this size's
(tests/test_reid_graph.py); the other limits are the cell's own."""

import importlib
import json
import os
import time

import pytest

from conftest import BENCH, FAKE_DEVICE

OPS = [{"op": "ObjectDetect",
        "args": {"width": 8, "num_classes": 2, "pretrained": False}},
       {"op": "TopBox"},
       {"op": "CropResize", "args": {"height": 32, "width": 16}},
       {"op": "FaceEmbedding",
        "args": {"width": 8, "dim": 16, "pretrained": False}},
       {"op": "IndexRow"}]
TINY = {"config": {"video": {"width": 128, "height": 96, "frames": 64},
                   "graph": {"ops": OPS},
                   "client": {"perf": {"frame_cache_mb": 8}}},
        "traffic": {"tables": 4, "resident_tables": 2,
                    "fill_bulk_tables": 2, "streams": 2}}
SMALL = {"DET_TIE": 0.5, "reid_box_err": 0.03, "reid_embed_gap": 0.1}
CONTROL_FAILS = {"fp8": ["reid_box_err", "reid_embed_gap", "reid_norm_err"],
                 "no_crop": ["reid_embed_gap"],
                 "other_box": ["reid_embed_gap"]}
NEW_METRICS = ("kernels.reid_step_mfu_pct", "kernels.crop_roofline",
               "kernels.nms_device_ms_per_row",
               "kernels.embed_device_ms_per_row",
               "evaluate.upload_ms_per_row")


@pytest.fixture()
def small(monkeypatch):
    from reference import ReidFeatures as R
    monkeypatch.setattr(R, "DET_TIE", SMALL["DET_TIE"])
    for k in ("reid_box_err", "reid_embed_gap"):
        monkeypatch.setitem(R.LIMITS, k, SMALL[k])
    return R


@pytest.fixture()
def run_reid(manifest, small):
    import harness

    def go(seed, seconds=1.0, trace=False):
        return harness.run_cell(manifest, "reid_dense", seed, seconds,
                                trace, time.time(), dict(FAKE_DEVICE),
                                overrides=TINY)
    return go


@pytest.mark.parametrize("seed", [2 ** 31 + 45, 9])
def test_reid_dense_reads_correct(run_reid, seed):
    r = run_reid(seed)
    assert r["correct"] and r["failed"] == 0, r["compared"]
    compared = {k: v["value"] for k, v in r["compared"].items()}
    assert compared.pop("reid_box_err") > 0
    assert compared.pop("reid_embed_gap") > 0
    assert compared.pop("reid_norm_err") < 1e-5
    assert set(compared.values()) == {0}
    assert set(r["metrics"]) == {"frames_per_s", "setup_s"}


def test_the_traced_run_reads_the_new_metrics(run_reid, manifest):
    import harness
    r = run_reid(2 ** 31 + 46, trace=True)
    assert r["correct"], r["compared"]
    got = {k: v["value"] for k, v in r["metrics"].items()}
    # TopBox's (k, 4) float32 boxes, put on the chip once a chunk
    assert got["evaluate.upload_ms_per_row"] > 0
    assert got["evaluate.reuse_pct"] == 100.0
    assert got["decode.frames_per_row"] == 1.0
    # the four that read the device trace have nothing to read on a CPU;
    # their files load and name what the harness has
    for name in NEW_METRICS:
        mdef = harness.load_json("metrics", name + ".json")
        entry, = [m for m in manifest["per_layer"] if m["name"] == name]
        assert entry["workloads"] == ["reid_dense"]
        assert {k: mdef[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} \
            == {k: entry[k] for k in ("unit", "better", "source", "layer",
                                      "moves")}
        assert os.path.exists(os.path.join(BENCH, "reducers",
                                           mdef["reducer"] + ".py"))
        if mdef["source"] == "device_trace":
            assert name not in got


def test_the_work_functions_count_the_cells_shapes():
    with open(os.path.join(BENCH, "configs", "reid_1080p.json")) as f:
        cfg = json.load(f)
    flops = importlib.import_module("work.reid_conv_flops")
    # the backbone at 1080p (68 x 120 cells of 128 channels), the two 3x3
    # heads of 9 anchors x (2 + 4), the backbone at 256x128, the dense
    macs, fh, fw, c = flops.backbone_macs(1080, 1920, 32)
    assert (fh, fw, c) == (68, 120, 128)
    head = 68 * 120 * 9 * 128 * 9 * 6
    emb = flops.backbone_macs(256, 128, 32)[0] + 128 * 128
    assert flops.work(cfg, 3) == {"flops": 2 * 3 * (macs + head + emb)}
    assert 29.2e9 < flops.work(cfg, 1)["flops"] < 29.3e9
    crop = importlib.import_module("work.reid_crop_bytes")
    assert crop.work(cfg, 2) == {"bytes": 2 * (6220800 + 98304)}


def _trace(events, scopes, marks=((0, 10), (990, 1000))):
    return {"devices": {"/device:TPU:0": events},
            "marks": {"/device:TPU:0": list(marks)},
            "scopes": {"/device:TPU:0": scopes}}


def test_a_scopes_loop_is_read_once():
    """A while of 400 ns holding three body operations of 100: `by_scope`
    would read 700 ns under `nms`, the operations that hold no other 300;
    an operation under two of the scopes counts once, and the window
    clips (a copy that runs 10 ns into it)."""
    from reducers import leaf_scope_ms_per_row as leaf
    loaded = _trace(
        [("while.1", 100, 500), ("fusion.2", 110, 210),
         ("fusion.3", 220, 320), ("reduce.4", 330, 430),
         ("conv.5", 600, 700), ("copy.6", 980, 1040)],
        {"while.1": "jit(infer)/ObjectDetect/nms/while",
         "fusion.2": "jit(infer)/ObjectDetect/nms/while/body/argmax",
         "fusion.3": "jit(infer)/ObjectDetect/nms/while/body/iou",
         "reduce.4": "jit(infer)/ObjectDetect/nms/while/body/max",
         "conv.5": "jit(infer)/ObjectDetect/Backbone_0/conv",
         "copy.6": "jit(infer)/ObjectDetect/nms/copy"})
    assert [e[0] for e in leaf.leaves(loaded["devices"]["/device:TPU:0"])] \
        == ["fusion.2", "fusion.3", "reduce.4", "conv.5", "copy.6"]
    # the body's 300 and the 10 of the copy inside the window
    assert leaf.leaf_seconds(loaded, ["nms"]) == pytest.approx(310e-9)
    assert leaf.leaf_seconds(loaded, ["nms", "ObjectDetect"]) \
        == pytest.approx(410e-9)
    assert leaf.leaf_seconds(loaded, ["Histogram"]) == 0.0
    # a traced run with no traced file up the call stack raises; an
    # untraced one reads nothing
    ctx = {"trace": {"by_scope": {}}, "rows": 8}
    over = importlib.import_module("reducers.work_over_scope")
    with pytest.raises(RuntimeError, match="trace_dir"):
        leaf.read(ctx, ["nms"])
    with pytest.raises(RuntimeError, match="trace_dir"):
        over.read(dict(ctx, cfg={}, peaks={}), "reid_crop_bytes",
                  "bytes", "hbm_bytes_per_s", "CropResize")
    ctx["trace"] = None
    assert leaf.read(ctx, ["nms"]) is None
    assert over.read(dict(ctx, cfg={}, peaks={}), "reid_crop_bytes",
                     "bytes", "hbm_bytes_per_s", "CropResize") is None


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 7])
def test_each_control_is_not_correct(manifest, small, seed):
    import reid_controls_on_chip
    recs = reid_controls_on_chip.controls(manifest, seed, overrides=TINY)
    assert [rec["control"] for rec in recs] == list(small.CONTROLS) + [None]
    for rec in recs[:-1]:
        assert rec["not_correct"], rec
        assert rec["over"] == CONTROL_FAILS[rec["control"]], rec
    # the program's own ops in the program's place
    prog = recs[-1]
    assert not prog["not_correct"], prog
    assert prog["lead"] <= small.DET_TIE and prog["corner"] < 0.01
    assert prog["odds_err"] < small.DET_TIE and prog["whole_frame"] == 0
