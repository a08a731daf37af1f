"""The cell of PR 34, `flow_ranges`, at 128x96 on the CPU (conftest.py's
`TINY` is keyed by traffic name and does not know `ranges_after`, so the
cut is here): the run goes through `graphs/sample_after.py` and
`outputs_kept` (which test_graphs_cpu.py holds) as the chip's does,
nothing sampled goes uncompared, the
traced run reads the counters of the window's gather and of the raw
column's write, and the control comes out as not correct through
`control_on_chip.py` as it stands.  Counts, not speeds."""

import time

import pytest

from conftest import FAKE_DEVICE

# the clip keeps its 256 rows, so a 32-row Range still has eight
# keyframe-aligned starts; eight tables come round every second request
TINY = {"config": {"video": {"width": 128, "height": 96},
                   "client": {"perf": {"frame_cache_mb": 8}}},
        "traffic": {"tables": 8}}
COUNTED = {"evaluate.gather_ms_per_row": None,
           "evaluate.gather_mb_per_row": 2 * 96 * 128 * 3 / 1e6,
           "save.raw_write_ms_per_row": None, "save.raw_mb_per_row": None,
           "decode.codec_frames_per_row": None,
           "staging.cache_hit_pct": None, "evaluate.pad_rows_per_row": 0.0}


@pytest.fixture()
def run_flow(manifest):
    import harness

    def go(seed, seconds=2.0, trace=False):
        return harness.run_cell(manifest, "flow_ranges", seed, seconds,
                                trace, time.time(), dict(FAKE_DEVICE),
                                overrides=TINY)
    return go


@pytest.mark.parametrize("seed", [2 ** 31 + 21, 5])
def test_flow_ranges_is_correct_and_nothing_goes_uncompared(run_flow, seed):
    r = run_flow(seed)
    assert r["correct"] and r["failed"] == 0, r["compared"]
    compared = {k: v["value"] for k, v in r["compared"].items()}
    assert 0 < compared.pop("flow_gap") < r["compared"]["flow_gap"]["limit"]
    assert set(compared.values()) == {0}
    assert set(r["metrics"]) == {"frames_per_s", "setup_s"}


def test_the_traced_run_reads_the_new_counters(run_flow):
    r = run_flow(2 ** 31 + 22, trace=True)
    assert r["correct"], r["compared"]
    got = {k: v["value"] for k, v in r["metrics"].items()}
    for name, exact in COUNTED.items():
        assert name in got, name
        if exact is not None:
            assert got[name] == pytest.approx(exact)
    # every field and its pickle framing; every row decoded at most
    # twice (its own GOP and the one before, for the window's reach)
    assert 96 * 128 * 2 * 4 / 1e6 < got["save.raw_mb_per_row"] < 0.0988
    assert 1.0 <= got["decode.codec_frames_per_row"] <= 2.0
    assert got["evaluate.gather_ms_per_row"] > 0
    assert got["save.raw_write_ms_per_row"] > 0


def test_the_control_is_not_correct_by_the_script_as_it_stands(manifest):
    """`control_on_chip.py` hands the reference neither the rows nor the
    window's wires: the first row of each sampled run past table row 0
    goes uncompared there, and `flow_gap` is what the precision moves."""
    import control_on_chip
    from reference import OpticalFlow as R
    rec = control_on_chip.control(manifest, "flow_ranges", 2 ** 31 + 23,
                                  overrides=TINY)
    assert rec["not_correct"] and rec["control"] == "bf16"
    assert rec["values"]["flow_gap"] > 3 * R.LIMITS["flow_gap"]
    assert rec["values"]["flow_rows_uncompared"] <= 3
    assert rec["values"]["flow_shape_errors"] == 0
    assert rec["values"]["flow_row0_nonzero"] == 0
