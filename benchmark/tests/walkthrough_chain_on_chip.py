"""The walkthrough's device chain on the chip, fused against staged: a
probe for PERF.md sec. 5, not a metric; run it through the chip tool:

    python3 benchmark/tests/walkthrough_chain_on_chip.py [--cell SECONDS] [--kernels 0]

A packet of the cell (16 seeded 1080p frames, as the wire conversion
leaves them on the chip) through `Resize` then `Grayscale` as the staged
evaluator calls them (two programs, the 480p RGB frame written and read
between) and through the one program `FusedKernelInstance` makes of
them; ms a row of each, the chain's temporaries, whether the two agree
and whether they are the reference's frames (every pixel counted); the
device `Grayscale` against its host flavour on all 2**24 colours; a
resize as two matrix products at `highest` precision, for comparison
(ROADMAP Speed L); and how the chain's result reaches the host: its
layout on the chip, `ColumnBatch.prefetch_host` + `to_host` a row, the
rows' contiguity.  With `--cell` it then runs the cell `walkthrough_dense`
traced for that many seconds with fusion switched off (here, by
`fusion.set_enabled(False)`: the cell has no switch) and prints its line:
the staged ops in the cell's own traffic.  One JSON line a reading.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]
T_START = time.time()

H, W, OH, OW, ROWS, REPEATS, SEED = 1080, 1920, 480, 640, 16, 10, 2147484001
CHAIN = "Resize+Grayscale"


def say(**rec):
    print(json.dumps(rec), flush=True)


def to_i420(rgb):
    """Any I420 frame will do for a wire: BT.601 studio swing, 2x2 means."""
    f = rgb.astype(np.float32)
    y = 16 + (65.738 * f[..., 0] + 129.057 * f[..., 1]
              + 25.064 * f[..., 2]) / 256
    u = 128 + (-37.945 * f[..., 0] - 74.494 * f[..., 1]
               + 112.439 * f[..., 2]) / 256
    v = 128 + (112.439 * f[..., 0] - 94.154 * f[..., 1]
               - 18.285 * f[..., 2]) / 256

    def sub(p):
        return p.reshape(H // 2, 2, W // 2, 2).mean((1, 3))
    return np.concatenate([np.rint(p).clip(0, 255).astype(np.uint8).ravel()
                           for p in (y, sub(u), sub(v))])


def timed(fn, *args):
    import jax
    jax.block_until_ready(fn(*args))  # compiles
    out = []
    for _ in range(REPEATS):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(1e3 * (time.perf_counter() - t) / ROWS)
    return {"ms_per_row_min": min(out), "ms_per_row_median":
            float(np.median(out))}


def kernels():
    import jax
    import jax.numpy as jnp

    import clipgen
    import scanner_tpu.kernels  # noqa: F401
    from reference import Walkthrough as R
    from reference import wire
    from scanner_tpu import DeviceType
    from scanner_tpu.engine import evaluate as ev
    from scanner_tpu.engine.batch import ColumnBatch
    from scanner_tpu.graph import ops as O
    from scanner_tpu.kernels import imgproc
    from scanner_tpu.kernels.color import yuv420_to_rgb_device

    src = clipgen.ClipSource(SEED, H, W)
    flat = np.stack([to_i420(src.frame(2 * i)) for i in range(ROWS)])
    rgb = jax.block_until_ready(yuv420_to_rgb_device(jnp.asarray(flat), H, W))
    say(reading="input", shape=list(rgb.shape),
        layout=list(rgb.format.layout.major_to_minor))

    members = []
    for name, args in (("Resize", {"width": OW, "height": OH}),
                       ("Grayscale", {})):
        k = O.registry.canonical_factory(O.registry.get(name))(
            O.KernelConfig(device=DeviceType.TPU, args=args), **args)
        members.append((name, k, 0))
    fused = jax.jit(lambda y: ev._trace_chain(CHAIN, members, y))

    def staged(y):
        for _, k, _ in members:
            y = jax.block_until_ready(k.execute(y))
        return y

    mem = fused.lower(rgb).compile().memory_analysis()
    say(reading="fused", temp_mb=mem.temp_size_in_bytes / 1e6,
        **timed(fused, rgb))
    say(reading="staged", **timed(staged, rgb))
    say(reading="resize_alone", **timed(members[0][1].execute, rgb))
    small = jax.block_until_ready(members[0][1].execute(rgb))
    say(reading="grayscale_alone", **timed(members[1][1].execute, small))

    a, b = np.asarray(fused(rgb)), np.asarray(staged(rgb))
    cfg = {"video": {"height": H, "width": W},
           "output": {"height": OH, "width": OW},
           "graph": {"ops": [{"args": {"replications": 3}}]}}
    want = np.stack([R.expected(f, cfg) for f in flat])
    gap = np.abs(a.astype(np.int16) - want)
    say(reading="agreement", fused_equals_staged=bool(np.array_equal(a, b)),
        pixels=int(a.size), differ_from_reference=int((gap > 0).sum()),
        largest_gap=int(gap.max()),
        rgb_differs=int((np.asarray(rgb) != np.stack(
            [wire.to_rgb(f, H, W) for f in flat])).sum()))

    v = np.arange(256, dtype=np.uint8)
    colours = np.stack(np.meshgrid(v, v, v, indexing="ij"), -1) \
        .reshape(16, 1024, 1024, 3)
    say(reading="grayscale_all_colours", differ=int(
        (np.asarray(imgproc._gray3_impl(jnp.asarray(colours)))
         != imgproc.gray3(colours)).sum()))

    # the same resize as two matrix products (the MXU's way): dense
    # weights from the reference's own taps, float32 at `highest`
    def dense(n, m):
        idx, wt = R.taps(n, m)
        out = np.zeros((m, n), np.float32)
        np.put_along_axis(out, idx, wt, 1)
        return jnp.asarray(out)
    wh, ww = dense(H, OH), dense(W, OW)

    @jax.jit
    def resize_mm(y):
        f = y.astype(jnp.float32)
        f = jnp.einsum("oh,bhwc->bowc", wh, f, precision="highest")
        f = jnp.einsum("pw,bowc->bopc", ww, f, precision="highest")
        return jnp.clip(jnp.round(f), 0, 255).astype(jnp.uint8)
    mm = np.asarray(resize_mm(rgb))
    say(reading="resize_as_matmuls",
        differ_from_resize=int((mm != np.asarray(small)).sum()),
        **timed(resize_mm, rgb))

    # how the chain's result reaches the host op
    out = jax.block_until_ready(fused(rgb))
    t = time.perf_counter()
    plain = np.asarray(out)
    plain_ms = 1e3 * (time.perf_counter() - t) / ROWS
    walls = []
    for _ in range(REPEATS):
        out = jax.block_until_ready(fused(rgb))
        t = time.perf_counter()
        host = ColumnBatch(np.arange(ROWS), out).prefetch_host().to_host()
        walls.append(1e3 * (time.perf_counter() - t) / ROWS)
    rows = [host.element_at(i) for i in range(ROWS)]
    say(reading="handoff", layout=list(out.format.layout.major_to_minor),
        sink_layout=ColumnBatch(np.arange(ROWS), out).sink_layout,
        plain_fetch_c_contiguous=bool(plain.flags.c_contiguous),
        plain_fetch_ms_per_row_cold=plain_ms,
        relaid_ms_per_row_min=min(walls),
        relaid_ms_per_row_median=float(np.median(walls)),
        c_contiguous=bool(host.data.flags.c_contiguous),
        rows_are_views=all(r.flags.c_contiguous
                           and np.shares_memory(r, host.data) for r in rows),
        equal=bool(np.array_equal(host.data, plain)))
    peak = jax.local_devices()[0].memory_stats() or {}
    say(reading="memory", peak_gb=peak.get("peak_bytes_in_use", 0) / 1e9)


def staged_cell(seconds):
    import harness
    import run as bench_run
    from scanner_tpu.graph import fusion
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    # the Client sets the switch from its configuration: off, and kept off
    fusion.set_enabled(False)
    fusion.set_enabled = lambda on: None
    result = harness.run_cell(manifest, "walkthrough_dense", SEED + 1,
                              seconds, True, T_START,
                              bench_run.find_device(1))
    say(reading="cell_staged", **{k: result[k] for k in (
        "correct", "metrics", "device", "compared")},
        device_ops=result.get("breakdown", {}).get("device_ops"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", type=float, default=0.0)
    ap.add_argument("--kernels", type=int, choices=(0, 1), default=1)
    args = ap.parse_args()
    import jax
    say(reading="device", backend=jax.default_backend(),
        kind=jax.local_devices()[0].device_kind)
    if args.kernels:
        kernels()
    if args.cell:
        staged_cell(args.cell)


if __name__ == "__main__":
    main()
