"""Tutorial 06: output compression (reference tutorials/06_compression.py).

Frame outputs re-encode to H.264 by default; .lossless() / .compress()
tune it, save_mp4 exports a playable file without re-encoding.

Upstream's tutorial filters every frame (Blur, kernel 3) and writes the
frame column back as video; this port shrinks to 320x240 instead, so
that a tutorial clip encodes in a moment.  The benchmark runs upstream's
graph at the source's own 1080p, with the default encode (libx264
veryfast, crf 20, keyint 16): configuration `blur_1080p`, cell
`blur_dense` (benchmark/configs/blur_1080p.json, PERF.md sec. 4), where
the save stage bounds the rate.
"""

import sys

from scanner_tpu import (CacheMode, Client, NamedVideoStream, PerfParams)
import scanner_tpu.kernels


def main():
    db_path = sys.argv[2] if len(sys.argv) > 2 else "/tmp/scanner_tpu_db"
    sc = Client(db_path=db_path)
    movie = NamedVideoStream(sc, "t06", path=sys.argv[1])
    frames = sc.io.Input([movie])
    small = sc.ops.Resize(frame=frames, width=[320], height=[240])
    out = NamedVideoStream(sc, "t06_small")
    sc.run(sc.io.Output(small.compress("video", crf=28), [out]),
           PerfParams.estimate(), cache_mode=CacheMode.Overwrite)
    out.save_mp4("/tmp/t06_small.mp4")
    print("wrote /tmp/t06_small.mp4")


if __name__ == "__main__":
    main()
