"""Bytes the Histogram graph has to move per row, from shapes alone: one
read of the frame as it sits in HBM (YUV420 wire, h*w*3/2 bytes) and one
write of its 3x16 int32 histogram.  Memory-bound: the compares and adds
are far below the chip's arithmetic peak."""


def work(cfg, rows):
    h, w = cfg["video"]["height"], cfg["video"]["width"]
    wire = h * w + 2 * ((h + 1) // 2) * ((w + 1) // 2)
    return {"bytes": rows * (wire + 3 * 16 * 4)}
