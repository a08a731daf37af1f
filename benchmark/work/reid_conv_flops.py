"""Floating-point operations the re-identification cascade's two networks
need per row, from shapes alone: 2 x the multiply-adds of their
convolutions and matmuls.  The detector runs on the whole frame (the
backbone, then the SSD head's class and box convolutions, 3x3 over the
stride-16 grid); the embedder on the crop (the backbone, then the dense
projection).  Norms, relus, the softmax, the box decode, the NMS scan and
the crop's resampling are left out: they are not the networks' work."""


def _same(n, stride):
    return -(-n // stride)


def backbone_macs(h, w, width):
    """Multiply-adds of the backbone on an (h, w) image, and its output
    grid (oh, ow) and channels."""
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
    return macs, oh, ow, cin


def macs_per_row(cfg):
    ops = {o["op"]: o.get("args", {}) for o in cfg["graph"]["ops"]}
    det, emb, crop = ops["ObjectDetect"], ops["FaceEmbedding"], \
        ops["CropResize"]
    anchors = len(cfg["graph"]["detector"]["anchor_scales"]) \
        * len(cfg["graph"]["detector"]["anchor_ratios"])
    v = cfg["video"]
    macs, oh, ow, c = backbone_macs(v["height"], v["width"], det["width"])
    macs += oh * ow * 9 * c * anchors * (det["num_classes"] + 4)
    e, _, _, c = backbone_macs(crop["height"], crop["width"], emb["width"])
    return macs + e + c * emb["dim"]


def work(cfg, rows):
    return {"flops": 2 * rows * macs_per_row(cfg)}
