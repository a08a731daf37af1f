"""Bytes the flow graph has to move per row, from shapes alone and
whatever implements the op: one read of the frame as it sits in HBM
(YUV420 wire, h*w*3/2 bytes; its predecessor was the row before's read)
and one write of the (h, w, 2) float32 field that the sink fetches.
The solver's sixteen passes over its float32 planes, the window's
copies and the conversion's planes are the program's choice and not
counted: that is what the share measures."""


def work(cfg, rows):
    h, w = cfg["video"]["height"], cfg["video"]["width"]
    wire = h * w + 2 * ((h + 1) // 2) * ((w + 1) // 2)
    return {"bytes": rows * (wire + h * w * 2 * 4)}
