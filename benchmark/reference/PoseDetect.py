"""Plain reference of the PoseDetect op at T = 1: the network's forward
pass in float32 `jax.numpy`/`lax` at `highest` matmul precision, and the
peak of each keypoint's heatmap.  Written from the architecture; imports
nothing of the program.  The weights are made here from the seed and
handed to the program as a weight file, so neither side takes anything
the other has made.

Architecture (width w, per frame of (H, W, 3) uint8):
  x/255 -> conv 7x7/4 (w) -> GroupNorm(8) -> relu
  -> 3 stages of 2 ResBlocks, channels w, 2w, 4w, stride 2 entering
     stages 1 and 2; ResBlock = conv3x3(/s) GN relu conv3x3 GN, skip is
     the input or a 1x1(/s) conv where shape changes, relu(skip + h)
  -> token = mean over space (C = 4w); 2 temporal blocks over a single
     token: x += proj(v) (softmax over one key is 1, so attention is its
     value), x += MoE(LayerNorm(x)), MoE = top-1 of 4 experts
     relu(x W1_e) W2_e
  -> feat *= 1 + film(token)
  -> 2 transposed convs 4x4/2 to 128 channels with relu -> 1x1 to K.
Output per keypoint: x, y of the heatmap's largest value and that value.
"""

import numpy as np

from reference import wire

KEYPOINTS = 17
HEADS_CH = 128
EXPERTS, EXPERT_HIDDEN = 4, 256
GN_GROUPS, EPS = 8, 1e-6

# name: limit.  Set from the readings in PERF.md (chip, PR 24): the
# program's largest over its seeds against the fp8 control's smallest.
LIMITS = {"pose_peak_gap": 0.05, "pose_score_err": 0.05}
MALFORMED = 1e30  # a row that is no (17, 3) of finite in-range peaks
# Router logits read about 1; bfloat16 operands moved them by up to 0.016
# and fp8 operands by 0.14 to 0.28 (23 seeds, 8 frames each, PERF.md), so
# a lead under 0.1 can change hands at the stated precision.
ROUTER_TIE = 0.1


def _param_shapes(width):
    """(path, shape) of every parameter, in the layout the op's weight
    file uses ('/'-joined flax names)."""
    out = []

    def conv(path, k, cin, cout):
        out.append((path + "/kernel", (k, k, cin, cout)))
        out.append((path + "/bias", (cout,)))

    def norm(path, c):
        out.append((path + "/scale", (c,)))
        out.append((path + "/bias", (c,)))

    def dense(path, cin, cout):
        out.append((path + "/kernel", (cin, cout)))
        out.append((path + "/bias", (cout,)))

    bb = "params/Backbone_0"
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
    C = cin
    for t in range(2):
        p = f"params/TemporalBlock_{t}"
        norm(p + "/LayerNorm_0", C)
        dense(p + "/qkv", C, 3 * C)
        dense(p + "/proj", C, C)
        norm(p + "/LayerNorm_1", C)
        dense(p + "/MoEMlp_0/router", C, EXPERTS)
        out.append((p + "/MoEMlp_0/w1", (EXPERTS, C, EXPERT_HIDDEN)))
        out.append((p + "/MoEMlp_0/w2", (EXPERTS, EXPERT_HIDDEN, C)))
    dense("params/film", C, C)
    hd = "params/DeconvHead_0"
    conv(hd + "/ConvTranspose_0", 4, C, HEADS_CH)
    conv(hd + "/ConvTranspose_1", 4, HEADS_CH, HEADS_CH)
    conv(hd + "/Conv_0", 1, HEADS_CH, KEYPOINTS)
    return out


def init_params(seed, width):
    """{path: float32 array}: kernels normal with variance 1/fan_in,
    biases 0, norm scales 1."""
    rng = np.random.default_rng([int(seed), int(width), 17])
    params = {}
    for path, shape in _param_shapes(width):
        leaf = path.rsplit("/", 1)[1]
        if leaf == "scale":
            a = np.ones(shape, np.float32)
        elif leaf == "bias":
            a = np.zeros(shape, np.float32)
        else:
            fan_in = int(np.prod(shape[:-1])) if leaf == "kernel" \
                else shape[-2]
            a = (rng.standard_normal(shape) / np.sqrt(fan_in)) \
                .astype(np.float32)
        params[path] = a
    return params


def make_op_args(cfg, seed, workdir):
    """Write the seeded weights where the op restores them from;
    {op name: arguments to add to that op of the graph}."""
    import os
    path = os.path.join(workdir, "pose_weights.npz")
    np.savez(path, **init_params(seed, cfg["graph"]["args"]["width"]))
    return {"PoseDetect": {"checkpoint_dir": path}}


def forward(params, frames, picks, precision="float32"):
    """(B, H, W, 3) uint8 -> ((B, h, w, K) float32 heatmaps, (2, B, E)
    router logits of the two temporal blocks).  `picks` (2, B) int32
    names each block's expert per frame; -1 leaves it to the router's
    largest logit.  `precision` "fp8" is the control: every convolution's
    and matmul's operands rounded to float8_e4m3, products summed in
    float32."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def q(a):
        if precision == "float32":
            return a
        if precision == "bfloat16":
            return a.astype(jnp.bfloat16).astype(jnp.float32)
        if precision == "fp8":
            return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        raise ValueError(precision)

    P = lambda path: jnp.asarray(params[path], jnp.float32)  # noqa: E731
    dn = ("NHWC", "HWIO", "NHWC")

    def conv(x, path, stride=1):
        y = lax.conv_general_dilated(
            q(x), q(P(path + "/kernel")), (stride, stride), "SAME",
            dimension_numbers=dn, precision=lax.Precision.HIGHEST)
        return y + P(path + "/bias")

    def deconv(x, path):
        y = lax.conv_transpose(
            q(x), q(P(path + "/kernel")), (2, 2), "SAME",
            dimension_numbers=dn, precision=lax.Precision.HIGHEST)
        return y + P(path + "/bias")

    def dense(x, path):
        y = jnp.matmul(q(x), q(P(path + "/kernel")),
                       precision=lax.Precision.HIGHEST)
        return y + P(path + "/bias")

    def group_norm(x, path):
        B, H, W, C = x.shape
        g = x.reshape(B, H, W, GN_GROUPS, C // GN_GROUPS)
        mean = g.mean(axis=(1, 2, 4), keepdims=True)
        var = ((g - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
        g = (g - mean) * lax.rsqrt(var + EPS)
        return g.reshape(B, H, W, C) * P(path + "/scale") \
            + P(path + "/bias")

    def layer_norm(x, path):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return (x - mean) * lax.rsqrt(var + EPS) * P(path + "/scale") \
            + P(path + "/bias")

    relu = jax.nn.relu
    bb = "params/Backbone_0"
    x = frames.astype(jnp.float32) / 255.0
    x = relu(group_norm(conv(x, bb + "/Conv_0", 4), bb + "/GroupNorm_0"))
    blk = 0
    for stage in range(3):
        for i in range(2):
            p = f"{bb}/ResBlock_{blk}"
            stride = 2 if (i == 0 and stage > 0) else 1
            h = relu(group_norm(conv(x, p + "/Conv_0", stride),
                                p + "/GroupNorm_0"))
            h = group_norm(conv(h, p + "/Conv_1"), p + "/GroupNorm_1")
            if p + "/Conv_2/kernel" in params:
                x = conv(x, p + "/Conv_2", stride)
            x = relu(x + h)
            blk += 1
    feat = x
    routers = []
    C = feat.shape[-1]
    tok = feat.mean(axis=(1, 2))  # (B, C): one token per frame
    for t in range(2):
        p = f"params/TemporalBlock_{t}"
        v = dense(layer_norm(tok, p + "/LayerNorm_0"), p + "/qkv")[:, 2 * C:]
        tok = tok + dense(v, p + "/proj")
        h = layer_norm(tok, p + "/LayerNorm_1")
        routers.append(dense(h, p + "/MoEMlp_0/router"))
        pick = jnp.where(picks[t] >= 0, picks[t],
                         jnp.argmax(routers[-1], axis=-1))
        w1 = P(p + "/MoEMlp_0/w1")[pick]  # (B, C, hidden)
        w2 = P(p + "/MoEMlp_0/w2")[pick]
        hid = relu(jnp.einsum("bc,bch->bh", q(h), q(w1),
                              precision=lax.Precision.HIGHEST))
        tok = tok + jnp.einsum("bh,bhc->bc", q(hid), q(w2),
                               precision=lax.Precision.HIGHEST)
    feat = feat * (1.0 + dense(tok, "params/film")[:, None, None, :])
    hd = "params/DeconvHead_0"
    x = relu(deconv(feat, hd + "/ConvTranspose_0"))
    x = relu(deconv(x, hd + "/ConvTranspose_1"))
    return conv(x, hd + "/Conv_0"), jnp.stack(routers)


def candidates(fwd, params, rgb):
    """The reference's heatmaps for a block of frames, as a list of
    (B, h, w, K) arrays: first as its own routing gives them; then, where
    a temporal block's best expert leads the runner-up by less than
    ROUTER_TIE on some frame, also with the runner-up on those frames
    (and so on in the blocks after it).  Top-1 routing is a discrete
    choice: within ROUTER_TIE it is decided by rounding at the stated
    precision, and either choice is the network's answer."""
    B, out = len(rgb), []

    def explore(forced):
        picks = np.full((2, B), -1, np.int32)
        for t, pick in enumerate(forced):
            picks[t] = pick
        heat, logits = fwd(params, rgb, picks)
        out.append(np.asarray(heat))
        order = np.argsort(-np.asarray(logits), axis=-1)  # (2, B, E)
        lead = np.take_along_axis(np.asarray(logits), order[..., :2], -1)
        for t in range(len(forced), 2):
            tied = lead[t, :, 0] - lead[t, :, 1] < ROUTER_TIE
            if tied.any():
                explore(forced + list(order[len(forced):t, :, 0])
                        + [np.where(tied, order[t, :, 1], order[t, :, 0])])

    explore([])
    return out


def peaks(heat):
    """(B, h, w, K) -> (B, K, 3) float32 [x, y, value] of each map's
    largest value."""
    heat = np.asarray(heat)
    B, h, w, K = heat.shape
    flat = heat.reshape(B, h * w, K)
    idx = flat.argmax(axis=1)
    val = np.take_along_axis(flat, idx[:, None, :], axis=1)[:, 0, :]
    return np.stack([idx % w, idx // w, val], -1).astype(np.float32)


def compare(cfg, wire_rows, outputs, control=None, block=4, seed=None,
            params=None):
    """`outputs[i]` is the (K, 3) the timed path committed for the frame
    whose wire is `wire_rows[i]`.  Widest gap, over rows and keypoints,
    by which the reference's heatmap at the committed peak lies below
    the reference's own best, and widest difference between the
    committed score and the reference's value there; both as a share of
    the largest |value| of the sample's reference heatmaps.  With
    `control` the reference in that precision stands in the program's
    place."""
    import jax

    h, w = cfg["video"]["height"], cfg["video"]["width"]
    if params is None:
        params = init_params(seed, cfg["graph"]["args"]["width"])
    fwd = jax.jit(forward, static_argnames="precision")
    natural = np.full((2, block), -1, np.int32)
    gap, err, top = 0.0, 0.0, 0.0
    for s in range(0, len(wire_rows), block):
        rgb = np.stack([wire.to_rgb(f, h, w) for f in wire_rows[s:s + block]])
        if len(rgb) < block:  # one compiled shape
            rgb = np.concatenate([rgb, np.repeat(rgb[-1:], block - len(rgb), 0)])
        heats = candidates(fwd, params, rgb)
        got = [np.asarray(o, np.float32) for o in outputs[s:s + block]] \
            if control is None else \
            list(peaks(fwd(params, rgb, natural, precision=control)[0]))
        top = max(top, float(np.abs(heats[0]).max()))
        for b, kp in enumerate(got):
            if kp.shape != (KEYPOINTS, 3) or not np.isfinite(kp).all():
                return dict.fromkeys(LIMITS, MALFORMED)
            xs, ys = kp[:, 0].astype(int), kp[:, 1].astype(int)
            if (xs < 0).any() or (ys < 0).any() \
                    or (xs >= heats[0].shape[2]).any() \
                    or (ys >= heats[0].shape[1]).any() \
                    or (xs != kp[:, 0]).any() or (ys != kp[:, 1]).any():
                return dict.fromkeys(LIMITS, MALFORMED)
            # the row is held to the candidate it lies closest to
            row = []
            for heat in heats:
                at = heat[b, ys, xs, np.arange(KEYPOINTS)]
                best = heat[b].reshape(-1, KEYPOINTS).max(axis=0)
                row.append((float((best - at).max()),
                            float(np.abs(kp[:, 2] - at).max())))
            row_gap, row_err = min(row, key=sum)
            gap, err = max(gap, row_gap), max(err, row_err)
    return {"pose_peak_gap": gap / top, "pose_score_err": err / top}


CONTROL = "fp8"
