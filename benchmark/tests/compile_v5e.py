"""Rehearsal 3: compile the cells' real sizes for a described (not
attached) v5e chip, from the program's own kernels and model:

    python3 benchmark/tests/compile_v5e.py

the wire conversion + Pallas histogram at 16 x 1080p (one work packet),
PoseDetect's network at 8 and 16 x 1080p, and the benchmark's float32
reference at 4 x 1080p.  What the TPU compiler refuses here it would
refuse on the chip.  A compile that passes is not a chip run.
"""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

H, W = 1080, 1920


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from reference import PoseDetect as R
    from scanner_tpu.kernels import pallas_ops
    from scanner_tpu.kernels.color import yuv420_to_rgb_device
    from scanner_tpu.models.pose import VideoPoseNet

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def report(what, fn, *args):
        t = time.time()
        mem = jax.jit(fn).lower(*args).compile().memory_analysis()
        print(f"{what}: compiled in {time.time() - t:.1f} s, temp "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB, arguments "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB", flush=True)

    def hist(flat):
        return pallas_ops.histogram_frames(
            yuv420_to_rgb_device(flat, H, W), interpret=False)

    report("wire -> RGB -> Pallas histogram, 16 x 1080p", hist,
           shape((16, H * W * 3 // 2), jnp.uint8))
    model = VideoPoseNet(width=32)
    tmpl = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 1, 128, 128, 3), jnp.uint8))
    params = jax.tree_util.tree_map(lambda a: shape(a.shape, a.dtype), tmpl)
    for b in (8, 16):
        report(f"PoseDetect network, {b} x 1080p", model.apply, params,
               shape((b, 1, H, W, 3), jnp.uint8))
    ref = {k: shape(v.shape, jnp.float32)
           for k, v in R.init_params(0, 32).items()}
    report("float32 reference, 4 x 1080p", R.forward, ref,
           shape((4, H, W, 3), jnp.uint8), shape((2, 4), jnp.int32))


if __name__ == "__main__":
    main()
