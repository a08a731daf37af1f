"""Optical flow app: dense per-frame flow fields over a video.
(Reference: examples/apps/optical_flow, whose OpticalFlow op is OpenCV's
Farneback flow in a CPU or GPU kernel.  Here the op of that name is a
jitted float32 Horn-Schunck solve on the device, sixteen fixed
iterations: another algorithm under the same name, the same [-1, 0]
stencil, so the engine decodes exactly one extra frame per task, and
the same column type, a (H, W, 2) float32 field a row; row 0 reads 0.)

Usage: python examples/optical_flow.py path/to/video.mp4 [db_path]
"""

import sys

import numpy as np

from scanner_tpu import (CacheMode, Client, NamedStream, NamedVideoStream,
                         PerfParams)
import scanner_tpu.kernels  # registers OpticalFlow


def main():
    video_path = sys.argv[1]
    db_path = sys.argv[2] if len(sys.argv) > 2 else "/tmp/scanner_tpu_db"
    sc = Client(db_path=db_path)

    movie = NamedVideoStream(sc, "flow-clip", path=video_path)
    frames = sc.io.Input([movie])
    flow = sc.ops.OpticalFlow(frame=frames)
    out = NamedStream(sc, "flow-fields")
    sc.run(sc.io.Output(flow, [out]), PerfParams.estimate(),
           cache_mode=CacheMode.Overwrite)

    mags = []
    for i, field in enumerate(out.load()):
        f = np.asarray(field)
        assert f.ndim == 3 and f.shape[2] == 2, f.shape
        mags.append(float(np.linalg.norm(f, axis=2).mean()))
    print(f"{len(mags)} flow fields; mean |flow| per frame: "
          f"min {min(mags):.3f} max {max(mags):.3f}")


if __name__ == "__main__":
    main()
