"""Plain reference of the frame-output graph Blur -> H.264 video column:
output row i is the Gaussian blur of source row i's RGB (the wire's
BT.601 conversion; `kernel_size` taps of exp(-x^2 / 2 sigma^2),
normalised, applied down the columns and then along the rows in
float32, edges replicated, rounded half to even), as it comes back from
the lossy encode the configuration states under `output`.

The committed frames went through a codec, so they cannot equal
`expected`.  They are held to three numbers, two of which nothing of
the program's encoder passes through:

`psnr_under_floor_db`: how far a sampled run's mean PSNR(committed,
`expected`) lies under the floor the configuration states
(`output.psnr_floor_db`).  An encode coarser than the stated one, in
the program's call or inside its codec binding, reads above 0 whatever
the reference's own encoder does: crf 26 stands 2.2 dB under crf 20.

`blur_response_missing`: the distance alone does not see the filter
left out, whose whole effect is smaller than the codec's noise (the
unfiltered frames, encoded as stated, stand 0.3 dB NEARER to `expected`
at 1080p).  What sees it is the filter's own direction.  With d =
`expected` - RGB (what the filter changes, known exactly), the share of
it that a frame carries is <frame - RGB, d> / <d, d>: 1.1-1.2 after the
stated encode (the codec smooths a little more), 0.5-0.6 where the
filter was left out.  The number is 1 less a run's mean share.

`psnr_deficit_db`: the reference encodes its own `expected` frames as
the program does, item by item (`output.item_rows` rows pass one encoder
of their own, from a keyframe), at the stated settings, decodes them,
and takes per row PSNR(its round trip, `expected`) - PSNR(committed,
`expected`); the larger of an item's mean and a tenth of the worst row.
x264 is deterministic, so a program that does the stated mathematics
and the stated encode commits the reference's own round trip and reads
0; twenty pixels a frame rounded the other way read under 0.006 on any
row, the filter computed in bfloat16 0.4-1.0.  Only whole items of the
sample take part: x264 looks ten frames ahead, so a part of an item
does not encode as the whole does.  This number shares the program's
encoder (libscvid is the one codec library on the machine), so a change
inside the binding moves both sides of it alike: the two numbers above
are what hold the encode.

Imports nothing of the engine but the codec binding,
`scanner_tpu.video.lib` (`Encoder`, `Decoder`), which the harness's own
decode of the wire and of the committed column already goes through;
its x264 preset (`veryfast`) is compiled into it.  Output row i is
taken to be source row i (the cells of this configuration sample
`All`), so an item starts where the source row is a multiple of
`output.item_rows`.
"""

from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

import clipgen
from reference import wire

# frames of the wrong shape or showing the wrong source row: none; the
# rest as the docstring has them (PERF.md sec. 2 has the readings on
# both sides of each limit)
LIMITS = {"frame_shape_errors": 0, "out_frame_id_errors": 0,
          "psnr_under_floor_db": 0.0, "blur_response_missing": 0.1,
          "psnr_deficit_db": 0.1}
# the reference's own filter in the nearest precision under the stated
# float32, encoded as stated, in the program's place.  The other two
# are broken guarantees, not precisions: the reference's round trip at a
# quantiser six crf steps coarser (the step a later PR would be tempted
# to take, since it encodes faster), and the filter left out.
CONTROL = "bf16"
CONTROLS = ("bf16", "crf26", "no_blur")
THREADS = 8
# a frame equal to `expected` (a lossless column) reads this, not inf
PSNR_CAP_DB = 100.0


def make_op_args(cfg, seed, workdir):
    return {}


def taps(kernel_size, sigma):
    r = (kernel_size - 1) / 2.0
    x = np.arange(kernel_size, dtype=np.float32) - np.float32(r)
    k = np.exp(-(x * x) / np.float32(2.0 * sigma * sigma))
    return (k / k.sum()).astype(np.float32)


def blur(rgb, kernel_size, sigma, dtype=np.float32):
    """(h, w, 3) uint8 -> (h, w, 3) uint8; `dtype` other than float32
    is the lower-precision control: the same sums at that precision."""
    k = taps(kernel_size, sigma).astype(dtype)
    pad = kernel_size // 2
    h, w = rgb.shape[:2]
    p = np.pad(rgb.astype(dtype), ((pad, pad), (pad, pad), (0, 0)),
               mode="edge")
    v = sum(k[i] * p[i:i + h] for i in range(kernel_size))
    o = sum(k[j] * v[:, j:j + w] for j in range(kernel_size))
    return np.clip(np.rint(o.astype(np.float32)), 0, 255).astype(np.uint8)


def expected(flat, h, w, kernel_size=3, sigma=0.5):
    return blur(wire.to_rgb(flat, h, w), kernel_size, sigma)


def luma(rgb):
    """BT.601 studio-swing luma of (h, w, 3) uint8 RGB."""
    r, g, b = (rgb[..., c].astype(np.int32) for c in range(3))
    return ((66 * r + 129 * g + 25 * b + 128) >> 8) + 16


def round_trip(frames, cfg, crf=None):
    """`frames` (a list of (h, w, 3) uint8) through one encoder at the
    configuration's stated settings and back, as RGB."""
    from scanner_tpu.video.lib import Decoder, Encoder
    out, v = cfg["output"], cfg["video"]
    h, w = frames[0].shape[:2]
    enc = Encoder(w, h, fps=v["fps"], codec=out["codec"],
                  crf=out["crf"] if crf is None else crf,
                  keyint=out["keyint"], bframes=out["bframes"])
    try:
        for f in frames:
            enc.feed(f)
        enc.flush()
        data, sizes, _, _, _ = enc.take_packets()
        dec = Decoder(enc.descriptor, enc.extradata, w, h)
    finally:
        enc.close()
    try:
        back = np.empty(len(frames) * h * w * 3, np.uint8)
        n, _, _ = dec.decode_run(data, sizes, np.ones(len(frames), np.uint8),
                                 back)
    finally:
        dec.close()
    if n != len(frames):
        raise RuntimeError(f"round trip gave {n} of {len(frames)} frames")
    return list(back.reshape(len(frames), h, w, 3))


def psnr(a, b):
    d = a.astype(np.int16) - b.astype(np.int16)
    mse = float(np.mean(np.square(d, dtype=np.int32)))
    if mse <= 0:
        return PSNR_CAP_DB
    return min(PSNR_CAP_DB, 10.0 * np.log10(255.0 ** 2 / mse))


def response(frame, rgb, want):
    """Share of the filter's change `want` - `rgb` that `frame` carries."""
    d = want.astype(np.float32) - rgb
    return float(((frame.astype(np.float32) - rgb) * d).sum()
                 / max(float((d * d).sum()), 1e-9))


def split(ids, cut):
    """[lo, hi) index ranges of `ids` that end where `cut(i)` holds
    between rows i - 1 and i."""
    cuts = [0] + [i for i in range(1, len(ids)) if cut(i)] + [len(ids)]
    return list(zip(cuts, cuts[1:]))


def compare(cfg, wire_rows, outputs, control=None, seed=None):
    """`outputs[i]` is the frame the timed path committed for the source
    row whose wire is `wire_rows[i]`; the sample's runs stand one after
    the other.  Returns {name: value} for LIMITS.  With `control` (one
    of CONTROLS) the reference's own round trip of its filter in
    bfloat16, at crf 26, or of the unfiltered frames stands in the
    program's place."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"no control {control!r}")
    h, w = cfg["video"]["height"], cfg["video"]["width"]
    args, out = cfg["graph"]["args"], cfg["output"]
    per, last = out["item_rows"], cfg["video"]["frames"] - 1

    def reduce(flat):
        rgb = wire.to_rgb(flat, h, w)
        return (clipgen.read_barcode(wire.planes(flat, h, w)[0]), rgb,
                blur(rgb, args["kernel_size"], args["sigma"]))

    # numpy and the codec release the interpreter lock inside their loops
    with ThreadPoolExecutor(THREADS) as pool:
        rows = list(pool.map(reduce, wire_rows))
        ids = [r[0] for r in rows]
        want = [r[2] for r in rows]
        # a run: consecutive source rows; a piece: a run's rows of one
        # item of the column; whole: from the item's first row to its
        # last (the column's last item ends with the clip).  Two sampled
        # runs that happen to follow on each other read as one: every
        # table holds the same clip, so the rows are the same frames
        runs = split(ids, lambda i: ids[i] != ids[i - 1] + 1)
        pieces = split(ids, lambda i: ids[i] != ids[i - 1] + 1
                       or ids[i] % per == 0)
        whole = [(lo, hi) for lo, hi in pieces if ids[lo] % per == 0
                 and (hi - lo == per or ids[hi - 1] == last)]
        own = dict(zip(whole, pool.map(
            lambda p: round_trip(want[p[0]:p[1]], cfg), whole)))
        if control is not None:
            src = {"bf16": lambda r: blur(r[1], args["kernel_size"],
                                          args["sigma"], ml_dtypes.bfloat16),
                   "crf26": lambda r: r[2], "no_blur": lambda r: r[1]}
            src = list(pool.map(src[control], rows))
            crf = 26 if control == "crf26" else None
            outputs = [f for part in pool.map(
                lambda p: round_trip(src[p[0]:p[1]], cfg, crf), pieces)
                for f in part]
        got = [np.asarray(f) for f in outputs]
        shaped = [f.shape == (h, w, 3) and f.dtype == np.uint8 for f in got]
        # a frame of the wrong shape has no distance: counted, left out
        dist = list(pool.map(
            lambda i: (psnr(got[i], want[i]), response(
                got[i], rows[i][1].astype(np.float32), want[i]))
            if shaped[i] else None, range(len(got))))
        # per whole item, how far each committed row stands under the
        # reference's own round trip of it
        lost = [part for part in pool.map(
            lambda p: [psnr(f, want[p[0] + i]) - dist[p[0] + i][0]
                       for i, f in enumerate(own[p]) if shaped[p[0] + i]],
            whole) if part]
        wrong_id = sum(pool.map(
            lambda i: shaped[i] and clipgen.read_barcode(luma(got[i]))
            != ids[i], range(len(got))))
    means = np.array([np.mean([d for d in dist[lo:hi] if d is not None]
                              or [(PSNR_CAP_DB, 1.0)], axis=0)
                      for lo, hi in runs] or [(PSNR_CAP_DB, 1.0)])
    return {"frame_shape_errors": shaped.count(False),
            "out_frame_id_errors": int(wrong_id),
            "psnr_under_floor_db": float(out["psnr_floor_db"]
                                         - means[:, 0].min()),
            "blur_response_missing": float(1.0 - means[:, 1].min()),
            "psnr_deficit_db": float(max(
                [np.mean(part) for part in lost]
                + [max(map(max, lost)) / 10.0])) if lost else 0.0}
