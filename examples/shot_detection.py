"""Shot detection app: histogram-difference boundaries + montage export.
(Reference: examples/apps/shot_detection.)  The benchmark's cell
`shot_dense` (benchmark/configs/shot_1080p.json) guards this graph.

Usage: python examples/shot_detection.py path/to/video.mp4
"""

import sys

import numpy as np

from scanner_tpu import (CacheMode, Client, NamedStream, NamedVideoStream,
                         PerfParams)
import scanner_tpu.kernels
from scanner_tpu.kernels.shot import detect_shots
from scanner_tpu import video as scv


def main():
    video_path = sys.argv[1]
    db_path = sys.argv[2] if len(sys.argv) > 2 else "/tmp/scanner_tpu_db"
    sc = Client(db_path=db_path)
    movie = NamedVideoStream(sc, "shots_movie", path=video_path)

    frames = sc.io.Input([movie])
    hists = sc.ops.Histogram(frame=frames)
    diffs = sc.ops.HistogramDelta(hist=hists)
    out = NamedStream(sc, "shot_diffs")
    sc.run(sc.io.Output(diffs, [out]), PerfParams.estimate(),
           cache_mode=CacheMode.Overwrite)

    d = np.asarray(list(out.load()))
    boundaries = detect_shots(d)
    print(f"{len(boundaries)} shot boundaries: {boundaries.tolist()}")

    # decode exactly one keyframe-exact frame per shot (minimal decode)
    if len(boundaries):
        reps = sc.load_frames("shots_movie", boundaries.tolist())
        print("representative frames:", reps.shape)


if __name__ == "__main__":
    main()
