"""Child process for tests/test_coststats.py: golden-pipeline warm-up
compile ledger on a virtual multi-device host.

Spawned with cpu_only_env(n_devices=2) + SCANNER_TPU_KERNEL_DEVICES=all
+ SCANNER_TPU_PRECOMPILE=1 so evaluator affinity assigns a chip per
pipeline instance and the bucket-ladder warm-up device_puts example
args — every warm-up rung then really compiles per chip, exactly like a
multi-chip TPU worker, and the compile ledger must account for each
(op, device, bucket).  Usage:

    python coststats_runner.py <video_path> <out_json>
"""

import json
import os
import sys
import tempfile


def main() -> int:
    video, out_path = sys.argv[1], sys.argv[2]
    from scanner_tpu import (CacheMode, Client, NamedStream,
                             NamedVideoStream, PerfParams)
    import scanner_tpu.kernels  # noqa: F401  (registers Histogram)
    from scanner_tpu.util import coststats
    import jax

    # a call is timed where its deferred wait saw it finish, which at
    # this size a CPU device never lets it: a chip still running at
    # every wait (tests/test_coststats.py says the same)
    coststats.result_ready = lambda res: False

    root = tempfile.mkdtemp(prefix="cseff_")
    sc = Client(db_path=os.path.join(root, "db"))
    sc.ingest_videos([("cs", video)])

    frame = sc.io.Input([NamedVideoStream(sc, "cs")])
    out = NamedStream(sc, "cs_hist")
    # wp=8 -> Histogram's warm ladder is bucket_ladder(8) = [4, 8]
    sc.run(sc.io.Output(sc.ops.Histogram(frame=frame), [out]),
           PerfParams.manual(8, 16), cache_mode=CacheMode.Overwrite,
           show_progress=False)
    rows = list(out.load())

    results = {
        "n_devices": len(jax.local_devices()),
        "n_rows": len(rows),
        "ledger": coststats.compile_ledger(),
        "summary": coststats.ledger_summary(),
        "op_efficiency": coststats.op_efficiency(),
        "report": sc.compile_report(),
    }
    sc.stop()
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1, default=str)
    print("COSTSTATS_OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
