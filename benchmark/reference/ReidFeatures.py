"""Plain reference of the re-identification cascade, ObjectDetect ->
TopBox -> CropResize -> FaceEmbedding, in float32 `jax.numpy`/`lax` at
`highest` matmul precision, computed in blocks of frames.  Written from
the equations below; imports nothing of the program.  The weights of both
networks are made here from the seed and handed to the program as weight
files, so neither side takes anything the other has made.

Backbone (width w, per image of (H, W, 3) uint8), as reference/
PoseDetect.py states it:
  x/255 -> conv 7x7/4 (w) -> GroupNorm(8) -> relu
  -> 3 stages of 2 ResBlocks, channels w, 2w, 4w, stride 2 entering
     stages 1 and 2; ResBlock = conv3x3(/s) GN relu conv3x3 GN, skip the
     input or a 1x1(/s) conv where the shape changes, relu(skip + h).
Detector: the backbone on the whole frame (a SAME-padded stride-16 grid
  of ceil(H/16) x ceil(W/16) cells), then two 3x3 convs: class logits
  (A x classes) and box deltas (A x 4) of A anchors a cell.  Anchor
  (cell y, x; scale s, ratio r), in that order: centre ((y + .5)/fh,
  (x + .5)/fw), size (s sqrt r, s / sqrt r) in units of the frame.
  Score: the largest softmax probability of a non-background class; its
  log-odds: that class's logit less the log-sum-exp of the others'.
  Box: centre + delta[0:2] x size, size x exp(clip(delta[2:4], -c, c)),
  corners [y1, x1, y2, x2] clipped to [0, 1].
  Greedy NMS, top_k steps: the highest score left (the first anchor of
  it) is kept while it is above 0, and every box whose IoU with it is
  over the threshold is dropped with it.  Kept boxes over the score
  threshold are the detections, strongest first.
TopBox: the first detection whose area is over 1e-6, else [0, 0, 1, 1].
Crop: output sample o of an axis of m samples, over the box's extent
  [lo, lo + e) of that axis in pixels (e = max(hi - lo, 1)), stands at
  c = (o + .5) e/m + lo - .5 and weighs input sample i by
  max(0, 1 - |c - i| / max(e/m, 1)), normalised over the frame's
  samples, 0 where c lies outside [-.5, n - .5]; both axes in one
  float32 contraction; rounded half to even, clipped to 0..255.
Embedder: the backbone on the crop, the mean over space, a dense layer
  to `dim`, divided by its L2 norm (by 1e-12 where that is smaller).

A committed row is the box TopBox took and that box's embedding,
[y1, x1, y2, x2, v_0 .. v_dim-1].  The box is held to the closest of its
candidates: every anchor whose reference score lies within DET_TIE of
the TopBox detection's, or over it, in log-odds (the whole frame too,
where no detection stood within DET_TIE of the threshold).  With random
weights the top anchor can change hands on rounding: either choice is
the network's answer (as PoseDetect.py's ROUTER_TIE).  So can TopBox's
area test, on a sliver clipped at the frame's edge: DET_TIE is measured
from the pick that test makes held EDGE inward, and a sliver over that
bar is a candidate.  Where the float32 score saturates at 1.0, every
anchor at 1.0 ties TopBox's exactly and TopBox orders them no way in
particular: the tie's weakest in log-odds is then the one DET_TIE is
measured from.  The tie is taken in log-odds because that is where the rounding acts: the
strongest scores stand at 0.995-0.9999999 (1 - p from 5e-3 to 1e-7 over
the seeds), and a tie of fixed width in p would hold one anchor a frame
on one seed and 300 on another.  The vector is held to the reference's
crop and embedding of the committed box on the frame, so the box's own
rounding, which a sub-pixel move of the crop carries into every value,
is read once, by the box.
"""

import functools

import numpy as np

from reference import wire

# name: limit, between the two readings of PERF.md sec. 2 (chip, three
# seeds of reid_controls_on_chip.py and the cell): the program's largest
# and the smallest of `fp8`'s, set midway in ratio on the first seven
# runs; twelve runs since leave each well between.  The box (max |corner
# - the closest candidate's|, unit coordinates): 0.00823 and 0.298.  The
# vector (L2 distance to the reference's embedding of the committed box):
# 0.00738 and 0.0447.  The norm: 3.6e-7 and 2.8e-3 (`fp8` normalises in
# bfloat16).  `no_crop` and `other_box` fail by the vector (0.119 and
# 0.139 at least).
LIMITS = {"reid_box_err": 0.04, "reid_embed_gap": 0.015,
          "reid_norm_err": 3e-5, "reid_shape_errors": 0}
MALFORMED = 1e30
# the program's log-odds at the box it took lie within 0.04-0.08 of the
# reference's where its box is one anchor's alone (chip, PERF.md sec. 2;
# within 0.035 with bfloat16 operands emulated on the CPU): a lead under
# DET_TIE can change hands
DET_TIE = 0.25
# the program's corners lie within 0.0082 of the reference's (chip, the
# largest over the cell's rows, PERF.md sec. 2): a box thinner than twice
# this may pass TopBox's area test on one side and fail it on the other,
# as a sliver clipped at the frame's edge did (0 high in the reference,
# 0.00026 in the program)
EDGE = 0.01
GN_GROUPS, EPS = 8, 1e-6
MIN_AREA = 1e-6
# the reference in the program's place: `fp8`, every stated precision one
# step down (both networks' convolution and matmul operands rounded to
# float8_e4m3, products summed in float32; the normalisation in
# bfloat16); `no_crop`, TopBox's box committed and the whole frame
# embedded (a crop that ignores its box); `other_box`, TopBox's box
# committed and the frame cropped at the box of the row OTHER rows on in
# the compared sample (boxes misaligned with their frames)
CONTROL = "fp8"
CONTROLS = ("fp8", "no_crop", "other_box")
OTHER = 16
DET_SEED, EMB_SEED = 45, 46
# The box head's kernel has a tenth of the others' spread: a trained SSD
# regresses deltas of the order of 0.1 (the box coder's centre variance
# 0.1).  At variance 1/fan_in the deltas are of the order of 1, the sizes'
# exp() makes most strongest boxes clip to the whole frame, and the crop
# would be a resize of the frame (PERF.md sec. 4).
BOX_HEAD, BOX_STD = "params/SSDHead_0/box/", 0.1


def _op(cfg, name):
    return next(o for o in cfg["graph"]["ops"] if o["op"] == name)


def _backbone_shapes(out, bb, width):
    def conv(path, k, cin, cout):
        out.append((path + "/kernel", (k, k, cin, cout)))
        out.append((path + "/bias", (cout,)))

    def norm(path, c):
        out.append((path + "/scale", (c,)))
        out.append((path + "/bias", (c,)))

    conv(bb + "/Conv_0", 7, 3, width)
    norm(bb + "/GroupNorm_0", width)
    cin, ch, blk = width, width, 0
    for stage in range(3):
        for i in range(2):
            p = f"{bb}/ResBlock_{blk}"
            stride = 2 if (i == 0 and stage > 0) else 1
            conv(p + "/Conv_0", 3, cin, ch)
            norm(p + "/GroupNorm_0", ch)
            conv(p + "/Conv_1", 3, ch, ch)
            norm(p + "/GroupNorm_1", ch)
            if cin != ch or stride != 1:
                conv(p + "/Conv_2", 1, cin, ch)
            cin, blk = ch, blk + 1
        ch *= 2
    return cin


def param_shapes(cfg):
    """{"det": [(path, shape)], "emb": [...]}: every parameter of the two
    networks, in the layout the ops' weight files use ('/'-joined flax
    names)."""
    d = _op(cfg, "ObjectDetect")["args"]
    e = _op(cfg, "FaceEmbedding")["args"]
    a = len(cfg["graph"]["detector"]["anchor_scales"]) \
        * len(cfg["graph"]["detector"]["anchor_ratios"])
    det, emb = [], []
    c = _backbone_shapes(det, "params/Backbone_0", d["width"])
    for name, n in (("cls", a * d["num_classes"]), ("box", a * 4)):
        det.append((f"params/SSDHead_0/{name}/kernel", (3, 3, c, n)))
        det.append((f"params/SSDHead_0/{name}/bias", (n,)))
    c = _backbone_shapes(emb, "params/Backbone_0", e["width"])
    emb.append(("params/Dense_0/kernel", (c, e["dim"])))
    emb.append(("params/Dense_0/bias", (e["dim"],)))
    return {"det": det, "emb": emb}


def init_params(seed, cfg):
    """{"det": {path: float32}, "emb": {...}}: kernels normal with
    variance 1/fan_in (the box head's BOX_STD**2/fan_in), biases 0, norm
    scales 1."""
    out = {}
    for net, tag in (("det", DET_SEED), ("emb", EMB_SEED)):
        rng = np.random.default_rng([int(seed), tag])
        params = {}
        for path, shape in param_shapes(cfg)[net]:
            leaf = path.rsplit("/", 1)[1]
            if leaf == "scale":
                params[path] = np.ones(shape, np.float32)
            elif leaf == "bias":
                params[path] = np.zeros(shape, np.float32)
            else:
                fan_in = int(np.prod(shape[:-1]))
                std = BOX_STD if path.startswith(BOX_HEAD) else 1.0
                params[path] = (std * rng.standard_normal(shape)
                                / np.sqrt(fan_in)).astype(np.float32)
        out[net] = params
    return out


def make_op_args(cfg, seed, workdir):
    """Write the seeded weights where the ops restore them from;
    {op name: arguments to add to that op of the graph}."""
    import os
    params = init_params(seed, cfg)
    out = {}
    for op, net in (("ObjectDetect", "det"), ("FaceEmbedding", "emb")):
        path = os.path.join(workdir, f"reid_{net}_weights.npz")
        np.savez(path, **params[net])
        out[op] = {"checkpoint_dir": path}
    return out


def anchors(cfg, h, w):
    """(fh * fw * A, 4) [cy, cx, ah, aw] of the frame's grid."""
    det = cfg["graph"]["detector"]
    s = det["stride"]
    fh, fw = -(-h // s), -(-w // s)
    size = [(sc * np.sqrt(r), sc / np.sqrt(r))
            for sc in det["anchor_scales"] for r in det["anchor_ratios"]]
    out = [((y + 0.5) / fh, (x + 0.5) / fw, ah, aw)
           for y in range(fh) for x in range(fw) for ah, aw in size]
    return np.asarray(out, np.float32)


@functools.lru_cache(maxsize=None)
def _nets():
    """The two forwards and the crop, jitted once a process:
    (detector, embedder, crop)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    dn = ("NHWC", "HWIO", "NHWC")

    def quant(precision):
        if precision == "float32":
            return lambda a: a
        if precision == "fp8":
            return lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        raise ValueError(precision)

    def conv(q, P, x, path, stride=1):
        y = lax.conv_general_dilated(
            q(x), q(P[path + "/kernel"]), (stride, stride), "SAME",
            dimension_numbers=dn, precision=hi)
        return y + P[path + "/bias"]

    def group_norm(P, x, path):
        B, H, W, C = x.shape
        g = x.reshape(B, H, W, GN_GROUPS, C // GN_GROUPS)
        mean = g.mean(axis=(1, 2, 4), keepdims=True)
        var = ((g - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
        g = (g - mean) * lax.rsqrt(var + EPS)
        return g.reshape(B, H, W, C) * P[path + "/scale"] + P[path + "/bias"]

    def backbone(q, P, images):
        relu = jax.nn.relu
        bb = "params/Backbone_0"
        x = images.astype(jnp.float32) / 255.0
        x = relu(group_norm(P, conv(q, P, x, bb + "/Conv_0", 4),
                            bb + "/GroupNorm_0"))
        blk = 0
        for stage in range(3):
            for i in range(2):
                p = f"{bb}/ResBlock_{blk}"
                stride = 2 if (i == 0 and stage > 0) else 1
                h = relu(group_norm(P, conv(q, P, x, p + "/Conv_0", stride),
                                    p + "/GroupNorm_0"))
                h = group_norm(P, conv(q, P, h, p + "/Conv_1"),
                               p + "/GroupNorm_1")
                if p + "/Conv_2/kernel" in P:
                    x = conv(q, P, x, p + "/Conv_2", stride)
                x = relu(x + h)
                blk += 1
        return x

    def detector(P, frames, anchors, classes, clip, precision="float32"):
        """-> ((B, N) scores, (B, N) their log-odds, (B, N, 4) boxes)."""
        q = quant(precision)
        feat = backbone(q, P, frames)
        B = feat.shape[0]
        logits = conv(q, P, feat, "params/SSDHead_0/cls") \
            .reshape(B, -1, classes)
        d = conv(q, P, feat, "params/SSDHead_0/box").reshape(B, -1, 4)
        scores = jax.nn.softmax(logits, axis=-1)[..., 1:].max(-1)
        best = logits[..., 1:].argmax(-1) + 1
        top = jnp.take_along_axis(logits, best[..., None], -1)[..., 0]
        rest = jnp.where(jax.nn.one_hot(best, classes, dtype=bool),
                         -jnp.inf, logits)
        odds = top - jax.nn.logsumexp(rest, axis=-1)
        cy = anchors[:, 0] + d[..., 0] * anchors[:, 2]
        cx = anchors[:, 1] + d[..., 1] * anchors[:, 3]
        bh = anchors[:, 2] * jnp.exp(jnp.clip(d[..., 2], -clip, clip))
        bw = anchors[:, 3] * jnp.exp(jnp.clip(d[..., 3], -clip, clip))
        boxes = jnp.stack([cy - bh / 2, cx - bw / 2, cy + bh / 2,
                           cx + bw / 2], -1)
        return scores, odds, jnp.clip(boxes, 0.0, 1.0)

    def embedder(P, crops, precision="float32"):
        q = quant(precision)
        pooled = backbone(q, P, crops).mean(axis=(1, 2))
        e = jnp.matmul(q(pooled), q(P["params/Dense_0/kernel"]),
                       precision=hi) + P["params/Dense_0/bias"]
        if precision != "float32":  # the normalisation a step down too
            e = e.astype(jnp.bfloat16)
        n = jnp.linalg.norm(e, axis=-1, keepdims=True)
        return (e / jnp.maximum(n, 1e-12)).astype(jnp.float32)

    def taps(n, m, lo, e):
        """(n, m) weights of an axis of n input samples made m over the
        extent [lo, lo + e) (the docstring's equation)."""
        c = (jnp.arange(m, dtype=jnp.float32) + 0.5) * (e / m) + lo - 0.5
        width = jnp.maximum(e / m, 1.0)
        wt = jnp.maximum(0.0, 1.0 - jnp.abs(
            c[None, :] - jnp.arange(n, dtype=jnp.float32)[:, None]) / width)
        wt = wt / jnp.maximum(wt.sum(0, keepdims=True), 1e-30)
        return jnp.where((c >= -0.5) & (c <= n - 0.5), wt, 0.0)

    def crop(frames, boxes, oh, ow):
        """(B, H, W, 3) uint8, (B, 4) unit [y1, x1, y2, x2] -> (B, oh, ow,
        3) uint8."""
        H, W = frames.shape[1:3]

        def one(f, b):
            y1, x1, y2, x2 = b[0] * H, b[1] * W, b[2] * H, b[3] * W
            wy = taps(H, oh, y1, jnp.maximum(y2 - y1, 1.0))
            wx = taps(W, ow, x1, jnp.maximum(x2 - x1, 1.0))
            out = jnp.einsum("hwc,ho,wp->opc", f.astype(jnp.float32), wy, wx,
                             precision=hi)
            return jnp.clip(jnp.round(out), 0, 255).astype(jnp.uint8)

        return jax.vmap(one)(frames, boxes)

    return (jax.jit(detector, static_argnames=("classes", "clip",
                                               "precision")),
            jax.jit(embedder, static_argnames="precision"),
            jax.jit(crop, static_argnames=("oh", "ow")))


def nms_order(scores, boxes, top_k, iou_thresh):
    """Indices the greedy NMS keeps, in the order it keeps them, and
    their scores (one frame: (N,) and (N, 4) float32)."""
    s = np.array(scores, np.float32)
    area = np.maximum(boxes[:, 2] - boxes[:, 0], 0) \
        * np.maximum(boxes[:, 3] - boxes[:, 1], 0)
    kept, kept_s = [], []
    for _ in range(top_k):
        j = int(np.argmax(s))
        if s[j] <= 0:
            break
        kept.append(j)
        kept_s.append(float(s[j]))
        b = boxes[j]
        inter = np.maximum(np.minimum(b[2], boxes[:, 2])
                           - np.maximum(b[0], boxes[:, 0]), 0) \
            * np.maximum(np.minimum(b[3], boxes[:, 3])
                         - np.maximum(b[1], boxes[:, 1]), 0)
        iou = inter / np.maximum(area[j] + area - inter, 1e-9)
        s[iou > iou_thresh] = -1.0
        s[j] = -1.0
    return kept, kept_s


def top_box(scores, boxes, det, inset=0.0):
    """(TopBox's box, the anchor it is, or None for the whole frame).
    With `inset`, a box has area only where it keeps more than MIN_AREA
    with each corner moved that far inward."""
    kept, kept_s = nms_order(scores, boxes, det["top_k"], det["iou_thresh"])
    for j, s in zip(kept, kept_s):
        b = boxes[j]
        h = max(b[2] - b[0] - 2 * inset, 0.0)
        if s > det["score_thresh"] \
                and h * max(b[3] - b[1] - 2 * inset, 0.0) > MIN_AREA:
            return b, j
    return np.asarray([0.0, 0.0, 1.0, 1.0], np.float32), None


def candidates(scores, odds, boxes, det):
    """The boxes a right program may have cropped from one frame:
    TopBox's box first, then every box whose score's log-odds stand at
    most DET_TIE under the bar's; the whole frame where no box of sure
    area stood DET_TIE over the threshold.  The bar is TopBox's pick with
    the area test held EDGE inward (a sliver at the frame's edge passes
    or fails that test on rounding), and the weakest anchor whose float32
    score ties it exactly (the score saturates at 1.0 past log-odds ~17,
    and TopBox orders a tie no way in particular)."""
    box, j = top_box(scores, boxes, det)
    out = [box]
    sure = top_box(scores, boxes, det, EDGE)[1]
    last = j if sure is None else sure
    floor = np.log(det["score_thresh"] / (1.0 - det["score_thresh"]))
    if last is not None:
        bar = float(odds[scores >= scores[last]].min()) - DET_TIE
        out += [boxes[k] for k in np.flatnonzero(odds >= bar)
                if not np.array_equal(boxes[k], box)]
    if sure is None or odds[sure] - DET_TIE <= floor:
        out.append(np.asarray([0.0, 0.0, 1.0, 1.0], np.float32))
    return out


def compare(cfg, wire_rows, outputs, control=None, seed=None, params=None,
            block=4):
    """`outputs[i]` is the (4 + dim,) row the timed path committed for the
    frame whose wire is `wire_rows[i]`: its box, then its vector.  Returns
    {name: value} for LIMITS: the largest distance of a row's box to the
    closest of its candidates, the largest L2 distance of a row's vector
    to the reference's embedding of the row's own box, the largest
    |norm - that embedding's norm|, and the rows that are no finite
    (4 + dim,) vector.  With `control` (one of CONTROLS) the reference so
    changed stands in the program's place."""
    import jax

    if control not in (None,) + CONTROLS:
        raise ValueError(f"no control {control!r}")
    v = cfg["video"]
    h, w = v["height"], v["width"]
    det = cfg["graph"]["detector"]
    classes = _op(cfg, "ObjectDetect")["args"]["num_classes"]
    dim = _op(cfg, "FaceEmbedding")["args"]["dim"]
    crop_args = _op(cfg, "CropResize")["args"]
    oh, ow = crop_args["height"], crop_args["width"]
    if params is None:
        params = init_params(seed, cfg)
    detector, embedder, crop = _nets()
    anc = anchors(cfg, h, w)
    n = len(wire_rows)
    whole = np.asarray([0.0, 0.0, 1.0, 1.0], np.float32)

    def blocks():
        """(first row, the block's RGB frames padded to `block`, rows)."""
        for s in range(0, n, block):
            rgb = np.stack([wire.to_rgb(f, h, w)
                            for f in wire_rows[s:s + block]])
            k = len(rgb)
            if k < block:  # one compiled shape
                rgb = np.concatenate([rgb, np.repeat(rgb[-1:], block - k, 0)])
            yield s, rgb, k

    def embed(rgb, boxes, precision="float32"):
        b = np.resize(np.asarray(boxes, np.float32), (block, 4))
        return np.asarray(embedder(params["emb"], crop(rgb, b, oh, ow),
                                   precision=precision))

    with jax.default_matmul_precision("highest"):
        # the detector on every frame: each row's candidates, TopBox's
        # box, and the box a control commits
        cands, tops, ctrl = [], [], []
        for s, rgb, k in blocks():
            sc, odds, bx = (np.asarray(a) for a in detector(
                params["det"], rgb, anc, classes, det["delta_clip"]))
            for b in range(k):
                cands.append(candidates(sc[b], odds[b], bx[b], det))
                tops.append(cands[-1][0])
            if control == "fp8":
                csc, _, cbx = (np.asarray(a) for a in detector(
                    params["det"], rgb, anc, classes, det["delta_clip"],
                    precision=control))
                ctrl += [top_box(csc[b], cbx[b], det)[0] for b in range(k)]
        # the committed rows: (box, the box the vector was cropped at, or
        # the vector itself)
        if control is None:
            rows = []
            for row in outputs:
                row = None if row is None else np.asarray(row, np.float32)
                ok = row is not None and row.shape == (4 + dim,) \
                    and bool(np.isfinite(row).all())
                rows.append((row[:4], row[4:]) if ok else None)
        elif control == "fp8":
            rows = [(b, b) for b in ctrl]
        elif control == "no_crop":
            rows = [(b, whole) for b in tops]
        else:
            shift = min(OTHER, n // 2)
            rows = [(b, tops[(i + shift) % n]) for i, b in enumerate(tops)]
        box_err = gap = norm_err = 0.0
        for s, rgb, k in blocks():
            part = rows[s:s + k]
            boxes = [r[0] if r is not None else whole for r in part]
            want = embed(rgb, boxes)
            if control is None:
                got = [r[1] if r is not None else None for r in part]
            else:
                got = embed(rgb, [r[1] for r in part],
                            "fp8" if control == "fp8" else "float32")
            for b, r in enumerate(part):
                if r is None:
                    continue
                box_err = max(box_err, min(
                    float(np.abs(c - boxes[b]).max()) for c in cands[s + b]))
                gap = max(gap, float(np.linalg.norm(got[b] - want[b])))
                norm_err = max(norm_err, abs(float(np.linalg.norm(got[b]))
                                             - float(np.linalg.norm(want[b]))))
    shape_errors = sum(r is None for r in rows)
    if shape_errors == n:
        return dict.fromkeys(LIMITS, MALFORMED)
    return {"reid_box_err": box_err, "reid_embed_gap": gap,
            "reid_norm_err": norm_err, "reid_shape_errors": shape_errors}
