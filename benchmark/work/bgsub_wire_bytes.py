"""Bytes the background-subtraction graph has to move per OUTPUT row,
from shapes alone and whatever implements the op: one read of the frame
as it sits in HBM (YUV420 wire, h*w*3/2 bytes); the row's 8-byte count
is nothing beside it.  The warm-up rows a task computes again, the
average image's read and write (24.9 MB each a computed row at 1080p)
and the conversion's planes are the program's choice and not counted:
that is what the share measures, as padding and halo rows lower
kernels.hist_roofline."""


def work(cfg, rows):
    h, w = cfg["video"]["height"], cfg["video"]["width"]
    return {"bytes": rows * (h * w + 2 * ((h + 1) // 2) * ((w + 1) // 2))}
