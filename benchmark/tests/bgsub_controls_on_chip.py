"""Both controls of `bgsub_dense` at the cell's own size, for the chip:

    python3 benchmark/tests/bgsub_controls_on_chip.py --seeds 1,2,3

`control_on_chip.py` runs a reference's `CONTROL` alone and hands
`compare` neither the sampled runs' rows nor the wires their window
reaches, so there a run that starts past table row 0 goes uncompared.
This script draws the same sample (as a run draws it, from the set-up
requests), hands `rows` and `window_wires` as `harness.decide_correct`
does, and puts each of the reference's `CONTROLS` in the program's
place: the state kept in bfloat16, and the state carried over the tasks'
starts.  Prints one JSON line per seed and control.  Each has to come
out as not correct, by `bg_count_gap` alone.
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

WORKLOAD = "bgsub_dense"


def controls(manifest, seed, overrides=None, workload=WORKLOAD):
    """One seed's records, one a control.  `overrides` shrinks the cell
    for a CPU test (harness.load_cell)."""
    import numpy as np

    import harness
    spec, cfg, traffic = harness.load_cell(manifest, workload, overrides)
    workdir = tempfile.mkdtemp(prefix="scbench_ctl_")
    try:
        cell = harness.Cell(cfg, traffic, seed, spec["chips"], workdir)
        ref = cell.reference
        covered = [{"request": r} for r in cell.plan["warm"]]
        sample = harness.check_sample(
            traffic, covered, np.random.default_rng([seed, 3]))
        wires, runs, halos = [], [], []
        for r, j, lo, hi in sample:
            stream = r["request"][j]
            halo = harness.window_rows(
                stream, lo, hi, ref.WINDOW,
                getattr(cell.builder, "WINDOW_OVER_TABLE", False),
                cfg["video"]["frames"])
            wires.extend(cell.wire(stream["table"], stream["rows"][lo:hi]))
            runs.append(list(stream["rows"][lo:hi]))
            halos.append(dict(zip(halo, cell.wire(stream["table"], halo)))
                         if halo else {})
        cell.sc.stop()
        out = []
        for control in ref.CONTROLS:
            values = ref.compare(cfg, wires, [None] * len(wires),
                                 control=control, seed=seed, rows=runs,
                                 window_wires=halos)
            over = sorted(k for k in ref.LIMITS
                          if values[k] > ref.LIMITS[k])
            out.append({"workload": workload, "seed": seed,
                        "rows": len(wires),
                        "runs_from": [run[0] for run in runs],
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
