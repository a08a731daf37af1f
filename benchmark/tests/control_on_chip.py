"""The control of a cell at the cell's own size, for the chip:

    python3 benchmark/tests/control_on_chip.py --workload <cell> --seeds 1,2,3

For each seed: the seeded clip, ingest, the sample of units that a run
would compare (drawn as a run draws it, from the set-up requests), their wire frames by the host
decode — then the plain reference computed in the control's lower
precision is put in the program's place and compared as a run compares.
Prints one JSON line per seed.  It has to come out as not correct.
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()

    import numpy as np

    import harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    spec, cfg, traffic = harness.load_cell(manifest, args.workload)
    harness.build_native()
    for seed in map(int, args.seeds.split(",")):
        workdir = tempfile.mkdtemp(prefix="scbench_ctl_")
        try:
            cell = harness.Cell(cfg, traffic, seed, spec["chips"], workdir)
            covered = [{"request": r} for r in cell.plan["warm"]]
            sample = harness.check_sample(
                traffic, covered, np.random.default_rng([seed, 3]))
            wires = [f for r, j, lo, hi in sample for f in cell.wire(
                r["request"][j]["table"], r["request"][j]["rows"][lo:hi])]
            ref = cell.reference
            values = ref.compare(cfg, wires, [None] * len(wires),
                                 control=ref.CONTROL, seed=seed)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "rows": len(wires),
                "control": str(ref.CONTROL), "values": values,
                "limits": ref.LIMITS,
                "not_correct": any(values[k] > ref.LIMITS[k]
                                   for k in ref.LIMITS)}), flush=True)
            cell.sc.stop()
        finally:
            shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    main()
