"""Bytes the Blur graph has to move per row, from shapes alone: one
read of the frame as it sits in HBM (YUV420 wire, h*w*3/2 bytes) and one
write of the filtered RGB frame (h*w*3 bytes) that the sink fetches.
Memory-bound: 3 + 3 multiply-adds a pixel-channel are far below the
chip's arithmetic peak.  The float32 copies the op makes on the way are
the program's choice and not counted."""


def work(cfg, rows):
    h, w = cfg["video"]["height"], cfg["video"]["width"]
    wire = h * w + 2 * ((h + 1) // 2) * ((w + 1) // 2)
    return {"bytes": rows * (wire + h * w * 3)}
