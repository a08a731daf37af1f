"""Bytes the walkthrough's device chain has to move per output row, from
shapes alone: one read of the source frame as it sits in HBM (YUV420
wire, h*w*3/2 bytes) and one write of the chain's result, the
(height, width, 3) uint8 frame the host op is handed.  The same work
whatever implements the chain, one fused program or one an op.
Memory-bound: ten taps and a luma a pixel are far below the chip's
arithmetic peak.  The RGB frame and the float32 intermediates between
are the program's choice and not counted."""


def work(cfg, rows):
    h, w = cfg["video"]["height"], cfg["video"]["width"]
    out = cfg["output"]
    wire = h * w + 2 * ((h + 1) // 2) * ((w + 1) // 2)
    return {"bytes": rows * (wire + out["height"] * out["width"] * 3)}
