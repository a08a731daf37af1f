"""Bytes the crop has to move per row, from shapes alone: one read of the
RGB frame it crops from, (height, width, 3) uint8 as the device op is
handed it, and one write of the (crop height, crop width, 3) uint8 crop:
6,220,800 + 98,304 B at 1080p and 256x128.  The op's weights span
every sample of the frame (0 outside the box), so it reads the frame
whole; one that gathered the box's rows alone would read less.  The
float32 weight matrices and intermediates are the program's choice and
not counted; the box is 16 B."""


def work(cfg, rows):
    v = cfg["video"]
    crop = next(o for o in cfg["graph"]["ops"]
                if o["op"] == "CropResize")["args"]
    return {"bytes": rows * 3 * (v["height"] * v["width"]
                                 + crop["height"] * crop["width"])}
