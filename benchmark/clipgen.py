"""Seeded synthetic video: textured panning background, moving shapes and
a frame-index barcode, so that it compresses like video (motion the
encoder can follow, residuals it has to code) and every frame can be
identified after a lossy encode.  The same seed gives the same frames.

Every seed gets the same structure: the same pan (odd in x and y, so
that chroma moves by half samples and P-frames carry residuals; an even
pan codes at half the bytes), the same shape sizes, the same texture
amplitude.  The seed sets phases, texture values, where the shapes are,
how they move and their colours — so decode work differs little from
seed to seed (chip, PR 24: 12.4-12.9 MB a 256-frame 1080p clip).
"""

import numpy as np

BAR_BITS = 16
N_SHAPES = 6
MARGIN = 256  # canvas overhang the pan moves through
PAN = (1, 3)  # px per frame, y and x


def bar_geometry(h, w):
    """(block size, x0, y0) of the barcode's 16 blocks, one row."""
    bs = max(4, w // 60)
    return bs, bs // 2, bs // 2


class ClipSource:
    """Frames of one clip, addressable by index."""

    def __init__(self, seed, height, width):
        self.h, self.w = int(height), int(width)
        rng = np.random.default_rng([int(seed), self.h, self.w])
        ch, cw = self.h + MARGIN, self.w + MARGIN
        phase = rng.uniform(0, 2 * np.pi, 3)
        yy = np.arange(ch, dtype=np.float32)[:, None]
        xx = np.arange(cw, dtype=np.float32)[None, :]
        canvas = np.empty((ch, cw, 3), np.float32)
        canvas[..., 0] = 128 + 70 * np.sin(xx * (2 * np.pi / cw) + phase[0])
        canvas[..., 1] = 128 + 70 * np.sin(yy * (2 * np.pi / ch) + phase[1])
        canvas[..., 2] = 128 + 70 * np.sin(
            (xx + yy) * (2 * np.pi / (cw + ch)) + phase[2])
        # band-limited texture: blocky noise at 1/8 resolution plus a
        # little fine grain, static on the canvas so that the pan is
        # pure motion
        coarse = rng.integers(-20, 21, (ch // 8 + 1, cw // 8 + 1, 3),
                              dtype=np.int8)
        canvas += coarse.repeat(8, axis=0).repeat(8, axis=1)[:ch, :cw]
        canvas += rng.integers(-4, 5, (ch, cw, 3), dtype=np.int8)
        self.canvas = np.clip(canvas, 0, 255).astype(np.uint8)
        self.pan = np.asarray(PAN)
        # the same six sizes for every seed, dealt out in a seeded order
        side = np.linspace(self.h / 10, self.h / 3, N_SHAPES).astype(int)
        self.size = np.stack([rng.permutation(side), rng.permutation(side)], 1)
        self.pos = rng.uniform(0, 1, (N_SHAPES, 2)) * (self.h, self.w)
        self.vel = rng.uniform(-6, 6, (N_SHAPES, 2)) * (self.h / 1080.0)
        self.color = rng.integers(0, 256, (N_SHAPES, 3), dtype=np.uint8)

    def frame(self, i):
        """(h, w, 3) uint8 RGB frame i."""
        i = int(i)
        oy, ox = (self.pan * i) % MARGIN
        f = self.canvas[oy:oy + self.h, ox:ox + self.w].copy()
        for s in range(N_SHAPES):
            y, x = (self.pos[s] + self.vel[s] * i) % (self.h, self.w)
            y, x = int(y), int(x)
            f[y:y + self.size[s, 0], x:x + self.size[s, 1]] = self.color[s]
        bs, x0, y0 = bar_geometry(self.h, self.w)
        for b in range(BAR_BITS):
            f[y0:y0 + bs, x0 + b * bs:x0 + (b + 1) * bs] = \
                255 if (i >> b) & 1 else 0
        return f

    def frames(self, n):
        return (self.frame(i) for i in range(n))


def read_barcode(luma):
    """Frame index from the luma plane (h, w) of a decoded frame."""
    h, w = luma.shape
    bs, x0, y0 = bar_geometry(h, w)
    q = max(1, bs // 4)  # read the block's core, off its coded edges
    idx = 0
    for b in range(BAR_BITS):
        core = luma[y0 + q:y0 + bs - q, x0 + b * bs + q:x0 + (b + 1) * bs - q]
        if core.mean() > 128:
            idx |= 1 << b
    return idx


def encode_clip(path, seed, n_frames, height, width, fps, keyint):
    """Write the seeded clip as H.264 in an .mp4 through the program's
    encoder (libx264 behind libscvid, the only codec library here)."""
    from scanner_tpu.video.ingest import encode_frames_mp4
    src = ClipSource(seed, height, width)
    encode_frames_mp4(path, src.frames(n_frames), width, height, fps=fps,
                      keyint=keyint)
