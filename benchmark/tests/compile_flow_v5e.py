"""Rehearsal 4: the OpticalFlow op's program, compiled for a described
(not attached) v5e chip at the flow cell's packet, must hold no bfloat16:

    python3 benchmark/tests/compile_flow_v5e.py

`_horn_schunck` at (4, 1080, 1920) float32 (what `flow_1080p` runs,
four rows a call), the luma program before it, and the stencil window's
gather at a 17-row chunk.  The configuration states float32 throughout;
a one-channel convolution at default precision compiled to bfloat16
operands here until PR 34 (PERF.md sec. 7).  Exits 1 on any `bf16` in
the optimised HLO of the op's two programs.  A compile that passes is
not a chip run.
"""

import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

H, W, BATCH, CHUNK = 1080, 1920, 4, 17


def compiled_for_v5e(fn, *shapes):
    """`fn` (jitted or not) compiled for one chip of a described v5e
    2x2; returns (executable, seconds)."""
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(dims, dtype, sharding=one)
            for dims, dtype in shapes]
    t = time.time()
    return jax.jit(fn).lower(*args).compile(), time.time() - t


def bf16_lines(executable):
    return [line.strip() for line in executable.as_text().splitlines()
            if re.search(r"\bbf16\b", line)]


def main():
    import jax.numpy as jnp

    from scanner_tpu.engine.evaluate import _window_gatherer
    from scanner_tpu.kernels.imgproc import _grayscale, _horn_schunck

    failed = 0
    for what, fn, shapes, held in (
            ("OpticalFlow luma, 4 x 1080p", _grayscale,
             [((BATCH, H, W, 3), jnp.uint8)], True),
            ("OpticalFlow solve, 4 x 1080p", _horn_schunck,
             [((BATCH, H, W), jnp.float32)] * 2, True),
            ("stencil window gather, 17-row chunk -> (4, 2)",
             _window_gatherer(),
             [((CHUNK, H, W, 3), jnp.uint8), ((BATCH, 2), jnp.int32)],
             False)):
        ex, seconds = compiled_for_v5e(fn, *shapes)
        mem = ex.memory_analysis()
        found = bf16_lines(ex) if held else []
        print(f"{what}: compiled in {seconds:.1f} s, temp "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB, arguments "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB, output "
              f"{mem.output_size_in_bytes / 1e9:.2f} GB"
              + (f", bf16 operands: {len(found)}" if held else ""),
              flush=True)
        for line in found[:8]:
            print("  " + line[:200])
        failed += bool(found)
    raise SystemExit(1 if failed else 0)


if __name__ == "__main__":
    main()
