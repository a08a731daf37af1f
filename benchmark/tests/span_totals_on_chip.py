"""What a run's spans add up to, per name, on the chip: sets a cell up as
the benchmark does, sends some of the window's requests and prints the
span totals (count, seconds) of the last two jobs' profiles.  A probe
for PERF.md §5, not a metric; run it through the chip tool:

    python3 benchmark/tests/span_totals_on_chip.py hist_dense:3 pose_dense:3
"""
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]
import harness  # noqa: E402


def main(args):
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    harness.build_native()
    for arg in args:
        cell_name, n = arg.split(":")
        spec, cfg, traffic = harness.load_cell(manifest, cell_name)
        workdir = tempfile.mkdtemp(prefix="scprobe_")
        try:
            cell = harness.Cell(cfg, traffic, 2147500301, spec["chips"], workdir)
            for req in cell.plan["warm"]:
                cell.run(req)
            recs = [cell.run(req)
                    for req, _ in zip(cell.plan["requests"], range(int(n)))]
            for rec in recs[-2:]:
                stats = cell.sc.get_profile(rec["job"]).statistics()
                stats.pop("_counters", None)
                print(json.dumps({
                    "cell": cell_name, "rows": rec["rows"],
                    "wall_s": rec["t_done"] - rec["t_call"],
                    "spans": {k: [v["count"], round(v["total_s"], 4)]
                              for k, v in stats.items()}}), flush=True)
            cell.sc.stop()
        finally:
            shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1:])
