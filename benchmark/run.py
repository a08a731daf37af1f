"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one run of one cell of BENCHMARK.json on the TPU that JAX
finds.  Prints the numbers compared beside their limits as the last
lines of standard error, and the result as one JSON object on the last
line of standard output.  Any backend but `tpu`, or fewer chips than the
cell asks for, ends the run with a non-zero code and no result.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]


def find_device(chips):
    """The chips this run may use, as JAX reports them; exits where they
    are not TPUs or too few."""
    import jax
    backend = jax.default_backend()
    found = jax.local_devices()
    if backend != "tpu" or len(found) < chips:
        print(f"[bench] needs {chips} TPU chip(s); JAX found backend "
              f"'{backend}' with {[str(d) for d in found]}. No result.",
              file=sys.stderr)
        raise SystemExit(3)
    return {"platform": found[0].platform, "kind": found[0].device_kind,
            "count": chips}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    import harness
    spec = harness.find_cell(manifest, args.workload)
    device = find_device(spec["chips"])
    result = harness.run_cell(manifest, args.workload, args.seed,
                              args.seconds, bool(args.trace), T_START,
                              device)
    for name, c in result["compared"].items():
        print(f"[bench] compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"[bench] correct: {result['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
