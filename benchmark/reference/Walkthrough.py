"""Plain reference of upstream's walkthrough graph, Stride -> Resize ->
Grayscale -> CloneChannels -> H.264 video column: output row i is source
row `stride` * i, its RGB (the wire's BT.601 conversion)

  resized to the stated size: output sample o of an axis of `n` samples
    made `m` stands at c = (o + 0.5) n/m - 0.5 and weighs input sample i
    by max(0, 1 - |c - i| / max(n/m, 1)), the weights of the samples
    inside the frame normalised to 1; down the columns first, then along
    the rows; float32, the taps added in the order of their samples;
    rounded half to even once, at the end, and clipped to 0..255;
  its luma (19595 R + 38470 G + 7471 B) >> 16 (0.299, 0.587, 0.114 in 16
    bits; integers, truncated);
  that one channel `replications` times;

as it comes back from the lossy encode the configuration states under
`output`.  The weights are computed here from the equation above, every
output sample against every input sample, and the taps read off them: no
table of the program's is shared.

The committed frames went through a codec and cannot equal `expected`.
They are held to numbers that each see one kind of fault, so that a
fault is reported once, by the number nearest its cause:

`frame_shape_errors`, `out_frame_id_errors`: frames that are not
(height, width, 3) uint8, and frames whose own barcode, read at the
resized geometry, is not their wire row's.  A frame of the wrong shape
has no distance and is left out of the rest.

`gray_channel_spread`: the largest |R - G| or |G - B| over the sampled
frames' pixels, as decoded: the guarantee of three equal channels.  A
frame over the limit has no one channel to take a distance of: it is
counted here and left out of the two distances below, which read a
frame's first channel.

`psnr_under_floor_db`: how far a sampled run's mean PSNR(committed,
`expected`) lies under the floor the configuration states
(`output.psnr_floor_db`); the reference's encoder is no part of it.  A
coarser encode or a coarser resize (no antialiasing) reads above 0.

`bf16_pattern_share`: a resize computed in bfloat16 puts a quarter of
the pixels one level off, 53 dB, under a codec that stands at 42: no
distance sees it (it moves an item's PSNR by -0.002 to 0.23 dB, and one
pixel a frame moves single rows by hundredths: x264 is deterministic
and chaotic).  What sees it is the pattern itself.  With d = the
reference's own frame computed in bfloat16 less `expected` (known
exactly), the share of d that a frame carries is
<frame - `expected`, d> / <d, d>, summed over an item's rows: 0.13-0.25
after the stated encode of `expected` itself (the codec's own error
leans the way rounding does), 0.15-0.23 more where the resize was in
bfloat16, 0.06 more where only its products were.  The number is the
largest, over the sample's whole items, of the committed item's share
less that of the reference's own round trip of the item: 0 for a
program that commits that round trip; under 0.005 for two hundred or
twenty thousand pixels a frame one level off.

`psnr_deficit_db`: the reference encodes its own `expected` frames as
the program does, item by item (`output.item_rows` output rows pass one
encoder of their own, from a keyframe), decodes them, and takes per row
PSNR(its round trip, `expected`) - PSNR(committed, `expected`); the
largest mean over an item.  A program that does the stated mathematics
and the stated encode commits the reference's own round trip and reads
0; twenty to two thousand pixels a frame one level off move an item's
mean by under 0.006.

Both take whole items of the sample only (x264 looks ahead, so a part
of an item does not encode as the whole does), and only items that
stand over the floor: one under it is counted there.  An item over the
pattern's limit is counted there and has no deficit.

Imports nothing of the engine but the codec binding (through
`reference/Blur.py`'s `round_trip`), as `Blur.py` does.
"""

from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

import clipgen
from reference import wire
from reference.Blur import PSNR_CAP_DB, psnr, round_trip, split

# PERF.md sec. 2 has the readings on both sides of each limit
LIMITS = {"frame_shape_errors": 0, "out_frame_id_errors": 0,
          "gray_channel_spread": 16, "psnr_under_floor_db": 0.0,
          "bf16_pattern_share": 0.03, "psnr_deficit_db": 0.015}
# the reference's own mathematics with one thing changed, encoded as
# stated, in the program's place: the resize's taps and sums in the
# nearest precision under the stated float32; the resize with no
# antialiasing (the triangle one input sample wide whatever the scale:
# two taps); the colour ops left out (the resized RGB committed)
CONTROL = "bf16"
CONTROLS = ("bf16", "nearest", "no_gray")
THREADS = 8
GRAY_WEIGHTS = (19595, 38470, 7471)


def make_op_args(cfg, seed, workdir):
    return {}


def taps(n, m, antialias=True):
    """(index, weight) of the input samples each of `m` output samples
    reads from `n`: (m, k) int and (m, k) float32, in the order of the
    samples, a sample of weight 0 where an output sample reads fewer
    than k."""
    scale = n / m
    c = (np.arange(m, dtype=np.float64) + 0.5) * scale - 0.5
    width = max(scale, 1.0) if antialias else 1.0
    dense = np.maximum(
        0.0, 1.0 - np.abs(c[:, None] - np.arange(n)[None, :]) / width)
    k = int((dense > 0).sum(1).max())
    first = np.minimum((dense > 0).argmax(1), n - k)
    idx = first[:, None] + np.arange(k)[None, :]
    wt = np.take_along_axis(dense, idx, 1)
    return idx, (wt / wt.sum(1, keepdims=True)).astype(np.float32)


def resize(rgb, oh, ow, antialias=True, dtype=np.float32):
    """(h, w, 3) uint8 -> (oh, ow, 3) uint8 by the docstring's equation;
    `dtype` other than float32 is the lower-precision control: every
    product and every sum rounded to it."""
    def at(x):
        return x if dtype is np.float32 \
            else x.astype(dtype).astype(np.float32)

    def axis(x, n, m):
        idx, wt = taps(n, m, antialias)
        out = np.zeros((m,) + x.shape[1:], np.float32)
        for j in range(idx.shape[1]):
            out = at(out + at(at(wt[:, j])[:, None, None] * x[idx[:, j]]))
        return out

    h, w = rgb.shape[:2]
    y = axis(at(rgb.astype(np.float32)), h, oh)
    o = axis(y.transpose(1, 0, 2), w, ow).transpose(1, 0, 2)
    return np.clip(np.rint(o), 0, 255).astype(np.uint8)


def luma(rgb):
    r, g, b = (rgb[..., c].astype(np.int32) for c in range(3))
    wr, wg, wb = GRAY_WEIGHTS
    return ((wr * r + wg * g + wb * b) >> 16).astype(np.uint8)


def clone(gray, replications):
    return np.dstack([gray] * replications)


def expected(flat, cfg):
    """The frame a wire row's output row is, before the encoder."""
    v, out = cfg["video"], cfg["output"]
    rgb = wire.to_rgb(flat, v["height"], v["width"])
    return clone(luma(resize(rgb, out["height"], out["width"])),
                 cfg["graph"]["ops"][-1]["args"]["replications"])


def read_barcode(gray, h, w):
    """The frame index from the one channel (oh, ow) of a resized frame:
    clipgen's barcode of the (h, w) source, each block read at the
    output samples whose centres fall in the block's core."""
    oh, ow = gray.shape
    bs, x0, y0 = clipgen.bar_geometry(h, w)
    q = bs / 4.0

    def core(lo, n, m):
        c = (np.arange(m) + 0.5) * n / m - 0.5
        inside = np.flatnonzero((c >= lo + q) & (c <= lo + bs - 1 - q))
        return inside if len(inside) else \
            np.array([int(np.abs(c - (lo + (bs - 1) / 2.0)).argmin())])

    ys = core(y0, h, oh)
    idx = 0
    for b in range(clipgen.BAR_BITS):
        xs = core(x0 + b * bs, w, ow)
        if gray[np.ix_(ys, xs)].mean() > 128:
            idx |= 1 << b
    return idx


def pieces(ids, per):
    """[lo, hi) ranges of a sample's output rows `ids`, cut where a run
    ends and where an item of `per` rows does: the parts of items that
    `compare` hands one encoder each."""
    return split(ids, lambda i: ids[i] != ids[i - 1] + 1
                 or ids[i] % per == 0)


def compare(cfg, wire_rows, outputs, control=None, seed=None):
    """`outputs[i]` is the frame the timed path committed for the source
    row whose wire is `wire_rows[i]`; the sample's runs stand one after
    the other.  Returns {name: value} for LIMITS.  With `control` (one
    of CONTROLS) the reference's own round trip of its changed
    mathematics stands in the program's place."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"no control {control!r}")
    v, out = cfg["video"], cfg["output"]
    h, w, oh, ow = v["height"], v["width"], out["height"], out["width"]
    stride, per = cfg["graph"]["stride"], out["item_rows"]
    copies = cfg["graph"]["ops"][-1]["args"]["replications"]
    last = (v["frames"] - 1) // stride

    def reduce(flat):
        rgb = wire.to_rgb(flat, h, w)
        return (clipgen.read_barcode(wire.planes(flat, h, w)[0]), rgb,
                clone(luma(resize(rgb, oh, ow)), copies),
                luma(resize(rgb, oh, ow, dtype=ml_dtypes.bfloat16)))

    # numpy and the codec release the interpreter lock inside their loops
    with ThreadPoolExecutor(THREADS) as pool:
        rows = list(pool.map(reduce, wire_rows))
        # output row of each sampled wire; a run: consecutive output
        # rows; a part: a run's rows of one item of the column; whole:
        # from the item's first row to its last (the column's last item
        # ends with the table)
        ids = [r[0] // stride for r in rows]
        want = [r[2] for r in rows]
        runs = split(ids, lambda i: ids[i] != ids[i - 1] + 1)
        parts = pieces(ids, per)
        whole = [(lo, hi) for lo, hi in parts if ids[lo] % per == 0
                 and (hi - lo == per or ids[hi - 1] == last)]
        own = dict(zip(whole, pool.map(
            lambda p: round_trip(want[p[0]:p[1]], cfg), whole)))
        if control is not None:
            src = {"bf16": lambda r: clone(r[3], copies),
                   "nearest": lambda r: clone(luma(resize(
                       r[1], oh, ow, antialias=False)), copies),
                   "no_gray": lambda r: resize(r[1], oh, ow)}
            src = list(pool.map(src[control], rows))
            outputs = [f for part in pool.map(
                lambda p: round_trip(src[p[0]:p[1]], cfg), parts)
                for f in part]
        got = [np.asarray(f) for f in outputs]
        shaped = [f.shape == (oh, ow, 3) and f.dtype == np.uint8
                  for f in got]
        spreads = [int(max(
            np.abs(f[..., 0].astype(np.int16) - f[..., 1]).max(),
            np.abs(f[..., 1].astype(np.int16) - f[..., 2]).max()))
            if ok else 0 for f, ok in zip(got, shaped)]
        gray = [ok and s <= LIMITS["gray_channel_spread"]
                for ok, s in zip(shaped, spreads)]
        dist = [psnr(got[i][..., 0], want[i][..., 0]) if gray[i] else None
                for i in range(len(got))]
        wrong_id = sum(
            shaped[i] and read_barcode(luma(got[i]), h, w) != rows[i][0]
            for i in range(len(got)))

    def mean(lo, hi):
        there = [d for d in dist[lo:hi] if d is not None]
        return float(np.mean(there)) if there else PSNR_CAP_DB

    def carried(frames, lo, hi):
        """Share of the bfloat16 rounding pattern that `frames`, an
        item's rows lo..hi of the sample, carry."""
        num = den = 0.0
        for i, f in zip(range(lo, hi), frames):
            if gray[i]:
                base = want[i][..., 0].astype(np.float32)
                d = rows[i][3].astype(np.float32) - base
                num += float(((f[..., 0].astype(np.float32) - base) * d).sum())
                den += float((d * d).sum())
        return num / max(den, 1e-9)

    floor = out["psnr_floor_db"]
    over = [(lo, hi) for lo, hi in whole if mean(lo, hi) >= floor]
    pattern = {(lo, hi): carried(got[lo:hi], lo, hi)
               - carried(own[lo, hi], lo, hi) for lo, hi in over}
    # per whole item that neither number above has taken, how far each
    # committed row stands under the reference's own round trip of it
    lost = [part for part in (
        [psnr(f[..., 0], want[lo + i][..., 0]) - dist[lo + i]
         for i, f in enumerate(own[lo, hi]) if gray[lo + i]]
        for lo, hi in over
        if pattern[lo, hi] <= LIMITS["bf16_pattern_share"]) if part]
    return {"frame_shape_errors": shaped.count(False),
            "out_frame_id_errors": int(wrong_id),
            "gray_channel_spread": max(spreads, default=0),
            "psnr_under_floor_db": float(
                floor - min([mean(lo, hi) for lo, hi in runs]
                            or [PSNR_CAP_DB])),
            "bf16_pattern_share": float(max(pattern.values(), default=0.0)),
            "psnr_deficit_db": float(max(np.mean(part) for part in lost))
            if lost else 0.0}
