"""The controls of `walkthrough_dense` at the cell's own size:

    python3 benchmark/tests/walkthrough_controls_on_chip.py --seeds 1,2,3

For each seed: the seeded clip, ingest, the sample a run would compare
(drawn as a run draws it, from the set-up request that has the cell's
own shape: the scans before it take every row, and this reference reads
a strided stream), its wire frames by the host decode.  Then each of the
reference's `CONTROLS` is put in the program's place, and last `None`:
the reference's own round trip, which is what a right program commits
(its `psnr_under_floor_db` is the reading the floor was set from).
Prints one JSON line per seed and control.  Each control has to come
out as not correct, by one number alone; `None` as correct.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

WORKLOAD = "walkthrough_dense"


def controls(manifest, seed, overrides=None, workload=WORKLOAD):
    """One seed's records, one a control and one for `None`.
    `overrides` shrinks the cell for a CPU test (harness.load_cell)."""
    import numpy as np

    import harness
    spec, cfg, traffic = harness.load_cell(manifest, workload, overrides)
    workdir = tempfile.mkdtemp(prefix="scbench_ctl_")
    try:
        cell = harness.Cell(cfg, traffic, seed, spec["chips"], workdir)
        ref = cell.reference
        covered = [{"request": r} for r in cell.plan["warm"]
                   if r[0]["sampler"] != "All"]
        sample = harness.check_sample(
            traffic, covered, np.random.default_rng([seed, 3]))
        wires = [f for r, j, lo, hi in sample for f in cell.wire(
            r["request"][j]["table"], r["request"][j]["rows"][lo:hi])]
        cell.sc.stop()
        out = []
        for control in ref.CONTROLS + (None,):
            if control is None:
                # a right program's items: the reference's own round trip
                # (piece by piece as `compare` cuts the sample: runs
                # that follow on each other read as one)
                frames = [ref.expected(f, cfg) for f in wires]
                ids = [i for _, _, lo, hi in sample for i in range(lo, hi)]
                outputs = [f for a, b in ref.pieces(
                    ids, cfg["output"]["item_rows"])
                    for f in ref.round_trip(frames[a:b], cfg)]
            else:
                outputs = [None] * len(wires)
            values = ref.compare(cfg, wires, outputs, control=control,
                                 seed=seed)
            over = sorted(k for k in ref.LIMITS
                          if values[k] > ref.LIMITS[k])
            out.append({"workload": workload, "seed": seed,
                        "rows": len(wires),
                        "runs_from": [r["request"][j]["rows"][lo]
                                      for r, j, lo, hi in sample],
                        "control": control, "values": values,
                        "limits": ref.LIMITS, "over": over,
                        "not_correct": bool(over)})
        return out
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()

    import harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    harness.build_native()
    for seed in map(int, args.seeds.split(",")):
        for rec in controls(manifest, seed):
            print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
