"""Each cell's whole run, after the look for a chip, at 128x96 on the
CPU: the plain references agree with the program's ops through
`Client.run`, the lower-precision controls do not, and a timed path
broken underneath comes out as not correct."""

import numpy as np
import pytest

CELLS = ["hist_dense", "hist_hot", "pose_dense"]


@pytest.mark.parametrize("cell", CELLS)
def test_reference_agrees_with_the_program(run_tiny, cell):
    r = run_tiny(cell)
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) >= {"frames_per_s", "setup_s"}
    assert ("job_p95_s" in r["metrics"]) == (cell == "hist_hot")
    assert list(r)[-1] == "compared"


def test_traced_run_reports_the_counts(run_tiny):
    r = run_tiny("hist_dense", trace=True)
    assert r["correct"], r["compared"]
    # no device plane on the CPU: the trace readers return nothing and
    # the line leaves their metrics out rather than print 0
    assert "kernels.hist_roofline" not in r["metrics"]
    assert "device.idle_pct" not in r["metrics"]
    assert r["metrics"]["decode.frames_per_row"]["value"] >= 0
    assert r["metrics"]["client.bulk_ramp_ms"]["value"] > 0


@pytest.mark.parametrize("shapes", [
    [{"sampler": "Stride", "stride": 8}],
    [{"sampler": "Gather", "count": 4, "stride": 8},
     {"sampler": "StridedRange", "count": 8, "stride": 4}]],
    ids=["stride", "gather_and_strided_range"])
def test_other_samplers_are_traffic_data(run_tiny, shapes):
    """Stride/Gather traffic needs a traffic file and no code: the
    generator names the program's stream op, the comparison follows the
    rows it asked for."""
    r = run_tiny("hist_hot", over={"traffic": {"shapes": shapes}})
    assert r["correct"] and r["failed"] == 0, r["compared"]


@pytest.mark.parametrize("sigma,correct", [(0.01, True), (1.0, False)])
def test_a_chain_of_ops_is_configuration_data(run_tiny, sigma, correct):
    """A graph of several ops is the configuration's `graph.ops`.  A blur
    that changes no pixel leaves the Histogram reference right; one that
    does is a different graph, and its own reference would be needed."""
    graph = {"ops": [{"op": "Blur", "args": {"kernel_size": 3,
                                             "sigma": sigma}},
                     {"op": "Histogram", "input": "frame"}],
             "reference": "Histogram"}
    r = run_tiny("hist_dense", over={"config": {"graph": graph}})
    assert r["failed"] == 0 and r["correct"] == correct, r["compared"]


def test_four_chip_cell_is_one_workloads_entry(run_tiny, manifest):
    """PERF.md's first open question, rehearsed on four virtual devices:
    `pose_dense_x4` is traffic/dense_x4.json (committed) and this entry."""
    import copy
    more = copy.deepcopy(manifest)
    more["workloads"].append({
        "name": "pose_dense_x4", "config": "pose_1080p",
        "traffic": "dense_x4", "chips": 4, "why": "rehearsal"})
    for m in more["per_layer"]:
        if "pose_dense" in m.get("workloads", []):
            m["workloads"].append("pose_dense_x4")
    r = run_tiny("pose_dense_x4", seconds=2.0, trace=True, manifest=more)
    assert r["correct"] and r["failed"] == 0, r["compared"]
    assert r["device"]["count"] == 4
    assert "decode.ms_per_frame" in r["metrics"]


def _wire_sample(n, h=96, w=128):
    import clipgen
    from reference import wire
    src = clipgen.ClipSource(5, h, w)
    rng = np.random.default_rng(0)
    # any I420 frame is a valid wire frame; chroma noise makes the
    # precision matter
    return [np.concatenate([
        src.frame(i)[..., 1].ravel(),
        rng.integers(0, 256, wire.wire_bytes(h, w) - h * w, dtype=np.uint8)])
        for i in range(n)]


def test_histogram_control_is_not_correct():
    from reference import Histogram as R
    cfg = {"video": {"height": 96, "width": 128}}
    flat = _wire_sample(4)
    exact = [R.expected(f, 96, 128) for f in flat]
    assert R.compare(cfg, flat, exact)["hist_rows_differ"] == 0
    assert R.compare(cfg, flat, exact,
                     control=R.CONTROL)["hist_rows_differ"] > 0


def test_pose_control_is_not_correct():
    import jax

    from reference import PoseDetect as R
    cfg = {"video": {"height": 96, "width": 128},
           "graph": {"args": {"width": 32}}}
    flat = _wire_sample(4)
    params = R.init_params(9, 32)
    from reference import wire
    rgb = np.stack([wire.to_rgb(f, 96, 128) for f in flat])
    natural = np.full((2, len(rgb)), -1, np.int32)
    bf16 = list(R.peaks(jax.jit(R.forward, static_argnames="precision")(
        params, rgb, natural, precision="bfloat16")[0]))
    same = R.compare(cfg, flat, bf16, params=params)
    assert all(same[k] <= R.LIMITS[k] for k in R.LIMITS), same
    ctl = R.compare(cfg, flat, bf16, control=R.CONTROL, params=params)
    assert any(ctl[k] > R.LIMITS[k] for k in R.LIMITS), ctl


def test_a_routing_tie_is_compared_under_either_choice(monkeypatch):
    """Output made with the runner-up expert in the second temporal block:
    wrong where the router's lead is clear, the network's answer where
    the lead is within ROUTER_TIE."""
    import jax

    from reference import PoseDetect as R
    from reference import wire
    cfg = {"video": {"height": 96, "width": 128},
           "graph": {"args": {"width": 32}}}
    flat = _wire_sample(4)
    params = R.init_params(9, 32)
    rgb = np.stack([wire.to_rgb(f, 96, 128) for f in flat])
    fwd = jax.jit(R.forward, static_argnames="precision")
    picks = np.full((2, len(rgb)), -1, np.int32)
    picks[1] = np.argsort(-np.asarray(fwd(params, rgb, picks)[1][1]))[:, 1]
    out = list(R.peaks(fwd(params, rgb, picks)[0]))
    monkeypatch.setattr(R, "ROUTER_TIE", 0.0)
    clear = R.compare(cfg, flat, out, params=params)
    assert any(clear[k] > R.LIMITS[k] for k in R.LIMITS), clear
    monkeypatch.setattr(R, "ROUTER_TIE", 1e9)
    tied = R.compare(cfg, flat, out, params=params)
    assert all(tied[k] < 1e-5 for k in R.LIMITS), tied


def _break(monkeypatch, op, how):
    """Breaks the op's kernel where it produces its answer."""
    import importlib
    mod = {"Histogram": "scanner_tpu.kernels.imgproc",
           "PoseDetect": "scanner_tpu.models.pose"}[op]
    cls = getattr(importlib.import_module(mod), op)
    orig = cls.execute

    def broken(self, frame):
        out = np.array(orig(self, frame))
        if how == "answer_altered":
            out[0, 0, -1] += 1
        elif how == "half_batch_left_out":
            out[len(out) // 2:] = 0
        return out

    monkeypatch.setattr(cls, "execute", broken)


@pytest.mark.parametrize("how", ["answer_altered", "half_batch_left_out"])
@pytest.mark.parametrize("cell,op", [("hist_dense", "Histogram"),
                                     ("hist_hot", "Histogram"),
                                     ("pose_dense", "PoseDetect")])
def test_broken_timed_path_is_not_correct(run_tiny, monkeypatch, cell, op,
                                          how):
    _break(monkeypatch, op, how)
    r = run_tiny(cell)
    assert not r["correct"], r["compared"]


def test_lost_rows_are_not_correct(run_tiny, monkeypatch):
    """A sink that commits a table short of its rows."""
    import harness
    real = harness.Cell.committed_rows
    monkeypatch.setattr(harness.Cell, "committed_rows",
                        lambda self, rec: real(self, rec) - 1)
    r = run_tiny("hist_dense")
    assert not r["correct"]
    assert r["compared"]["rows_missing"]["value"] > 0


def test_command_refuses_any_backend_but_tpu():
    """The command itself, no chip in sight: non-zero, no result line."""
    import os
    import subprocess
    import sys

    from conftest import ROOT
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "hist_dense", "--seed", str(2 ** 31 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 3
    assert done.stdout.strip() == ""
    assert "No result" in done.stderr
