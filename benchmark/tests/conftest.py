"""The benchmark's own tests run by hand and in rehearsal (they are not
part of the repo's tier-1 suite): on the CPU, at 128x96, on four virtual
devices, with the switches that make the engine take its accelerator
path there (YUV420 wire converted on the device, per-device pipeline
instances, ladder warm-up).  Nothing here is a device measurement.

    python3 -m pytest benchmark/tests -q
"""

import atexit
import os
import shutil
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["SCANNER_TPU_KERNEL_DEVICES"] = "all"
os.environ["SCANNER_TPU_YUV_DEVICE"] = "force"
os.environ["SCANNER_TPU_PRECOMPILE"] = "1"
os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="scbench_test_jaxcache_")
atexit.register(shutil.rmtree, os.environ["JAX_COMPILATION_CACHE_DIR"],
                ignore_errors=True)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import json  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

# a cell cut to what a test run can hold: same graph, same traffic kind
SMALL = {"video": {"width": 128, "height": 96, "frames": 64},
         "client": {"perf": {"frame_cache_mb": 8}}}
TINY = {
    "dense": {"config": SMALL,
              "traffic": {"tables": 4, "resident_tables": 2,
                          "fill_bulk_tables": 2, "streams": 2}},
    "dense_x4": {"config": SMALL,
                 "traffic": {"tables": 2, "resident_tables": 1,
                             "fill_bulk_tables": 1, "streams": 1}},
    "hot": {"config": SMALL,
            "traffic": {"tables": 2, "fill_bulk_tables": 2,
                        "shapes": [{"sampler": "Range", "count": 32},
                                   {"sampler": "Range", "count": 64}]}},
}
# stands in for the chip the harness would have looked for; no number of
# a CPU run is reported under a device metric's name
FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture(scope="session")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture()
def run_tiny(manifest):
    """Drives the rest of a run after the look for a chip."""
    import harness

    def go(cell, seed=2 ** 31 + 7, seconds=1.0, trace=False, over=None,
           manifest=manifest):
        spec = harness.find_cell(manifest, cell)
        return harness.run_cell(
            manifest, cell, seed, seconds, trace, time.time(),
            dict(FAKE_DEVICE, count=spec["chips"]),
            overrides=harness.merge(TINY[spec["traffic"]], over))
    return go
