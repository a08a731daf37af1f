"""How a sink batch reaches the host, on the chip: for a task's result
of `blur_dense` (32 x 1080p uint8 frames out of `_blur_impl`) and of
`flow_ranges` (32 x (1080, 1920, 2) float32 fields out of the flow
solve), each put together as the evaluator does, it prints the array's
layout on the device, the fetched array's strides, what a saver pays a
row for it (the encoder's `np.ascontiguousarray`, the raw item's
`pickle.dumps`), then the same after `ColumnBatch.prefetch_host` has
laid the batch out row-major, with the program's time on the device by
scope and the memory's peak.  A probe for PERF.md §5, not a metric; run
it through the chip tool:

    python3 benchmark/tests/sink_layout_on_chip.py
"""
import json
import os
import pickle
import shutil
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]
import harness  # noqa: E402
import trace_reduce  # noqa: E402

H, W, ROWS = 1080, 1920, 32
REPEATS = 5


def results():
    """(name, a task's result on the device), one at a time."""
    import jax
    import jax.numpy as jnp

    from scanner_tpu.engine.batch import row_programs
    from scanner_tpu.kernels import imgproc
    join = row_programs("columnbatch").concat
    frames = [jax.device_put(np.random.default_rng(i).integers(
        0, 256, (16, H, W, 3), np.uint8)) for i in range(ROWS // 16)]
    kern = jnp.asarray(imgproc._gaussian_kernel1d(3, 0.5))
    yield "blur", join(*[imgproc._blur_impl(f, kern, 3) for f in frames])
    gray = [imgproc._grayscale(f) for f in frames]
    del frames
    yield "flow", join(*[
        imgproc._horn_schunck(g[i:i + 4], jnp.roll(g[i:i + 4], 3, 2))
        for g in gray for i in range(0, 16, 4)])


def saver_ms_per_row(host):
    """What a saver's consumers pay a row of this host array, ms."""
    rows = list(host[:8])
    t = time.time()
    for r in rows:
        np.ascontiguousarray(r)
    contiguous = (time.time() - t) / len(rows)
    t = time.time()
    for r in rows:
        pickle.dumps(np.asarray(r), protocol=pickle.HIGHEST_PROTOCOL)
    return {"ascontiguousarray": round(1e3 * contiguous, 3),
            "pickle": round(1e3 * (time.time() - t) / len(rows), 3)}


def fetched(data):
    t = time.time()
    host = np.asarray(data)
    return host, round(1e3 * (time.time() - t) / len(host), 3)


def main():
    import jax

    from scanner_tpu.engine.batch import (ColumnBatch, merged_row_shape,
                                          row_programs)
    device = jax.local_devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"no chip: {device.platform}")
    stamp = harness.window_stamp([device])
    stamp()
    for name, data in results():
        data.block_until_ready()
        line = {"result": name, "shape": list(data.shape),
                "dtype": str(data.dtype),
                "layout": str(data.format.layout.major_to_minor)}
        host, line["fetch_ms_per_row"] = fetched(data)
        line.update(strides=list(host.strides),
                    c_contiguous=bool(host.flags.c_contiguous),
                    saver_ms_per_row=saver_ms_per_row(host))
        print(json.dumps({"as the chip laid it out": line}), flush=True)

        program = row_programs("columnbatch").rowmajor
        merged = merged_row_shape(data.shape)
        program(data, merged).block_until_ready()  # compiles
        trace_dir = tempfile.mkdtemp(prefix="scprobe_trace_")
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            stamp()
            t = time.time()
            for _ in range(REPEATS):
                program(data, merged).block_until_ready()
            wall = (time.time() - t) / REPEATS
            stamp()
            jax.profiler.stop_trace()
            reduced = trace_reduce.reduce_trace(trace_reduce.load(
                trace_reduce.find_xplane(trace_dir)))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        batch = ColumnBatch(np.arange(ROWS), data)
        before = batch.sink_layout
        batch.prefetch_host()
        relaid, line = batch.data, {"result": name}
        line.update(sink_layout=[before, batch.sink_layout],
                    shape=list(relaid.shape),
                    layout=str(relaid.format.layout.major_to_minor),
                    program_wall_ms_per_row=round(1e3 * wall / ROWS, 4),
                    program_device_ms_per_row={
                        k: round(1e3 * v / REPEATS / ROWS, 4)
                        for k, v in reduced["by_scope"].items()},
                    device_busy_ms_per_row=round(
                        1e3 * reduced["busy_s"] / REPEATS / ROWS, 4),
                    device_ops=[[k[:60], round(1e3 * v / REPEATS / ROWS, 4)]
                                for k, v in reduced["device_ops"][:4]])
        again = program(data, merged)
        again.block_until_ready()
        _, line["fetch_ms_per_row"] = fetched(again)
        del again, data
        after = batch.to_host().data
        line.update(strides=list(after.strides),
                    c_contiguous=bool(after.flags.c_contiguous),
                    rows_contiguous=all(r.flags.c_contiguous for r in after),
                    equal=bool(np.array_equal(after, host)),
                    saver_ms_per_row=saver_ms_per_row(after),
                    peak_gb=round(device.memory_stats()[
                        "peak_bytes_in_use"] / 1e9, 3))
        print(json.dumps({"laid out row-major": line}), flush=True)
        del batch, relaid, after, host


if __name__ == "__main__":
    main()
