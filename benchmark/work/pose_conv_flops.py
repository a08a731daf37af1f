"""Floating-point operations the PoseDetect network needs per frame,
from shapes alone: 2 x the multiply-adds of its convolutions, transposed
convolutions and matmuls (norms, relus, the mean and the argmax left
out).  A transposed convolution counts its true products (each input
pixel meets every tap once), not those of its zero-stuffed lowering."""

HEAD_CH, KEYPOINTS, EXPERTS_HIDDEN = 128, 17, 256


def _same(n, stride):
    return -(-n // stride)


def macs_per_frame(h, w, width):
    macs = 0
    oh, ow = _same(h, 4), _same(w, 4)
    macs += oh * ow * width * 7 * 7 * 3
    cin, ch = width, width
    for stage in range(3):
        for i in range(2):
            stride = 2 if (i == 0 and stage > 0) else 1
            oh, ow = _same(oh, stride), _same(ow, stride)
            macs += oh * ow * ch * 9 * cin          # 3x3 (strided)
            macs += oh * ow * ch * 9 * ch           # 3x3
            if cin != ch or stride != 1:
                macs += oh * ow * ch * cin          # 1x1 skip
            cin = ch
        ch *= 2
    C = cin
    # two temporal blocks on one token: qkv, proj, router, one expert
    macs += 2 * (C * 3 * C + C * C + C * 4 + 2 * C * EXPERTS_HIDDEN)
    macs += C * C                                   # film
    macs += oh * ow * 16 * C * HEAD_CH              # 4x4/2 transposed
    oh, ow = oh * 2, ow * 2
    macs += oh * ow * 16 * HEAD_CH * HEAD_CH
    oh, ow = oh * 2, ow * 2
    macs += oh * ow * HEAD_CH * KEYPOINTS           # 1x1
    return macs


def work(cfg, rows):
    v = cfg["video"]
    width = cfg["graph"]["args"]["width"]
    return {"flops": 2 * rows * macs_per_frame(v["height"], v["width"], width)}
