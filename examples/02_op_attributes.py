"""Tutorial 02: op attributes (reference tutorials/02_op_attributes.py).

An op's registration declares how the engine schedules it:

  batch=N           the kernel receives N-row batches — on TPU this is the
                    XLA batch dimension; PerfParams.work_packet_size tunes
                    the actual chunk within the declared cap
  stencil=[...]     each output row sees a window of input rows
                    (REPEAT_EDGE at the boundaries)
  bounded_state=W   stateful with warmup W: the engine replays W rows
                    before each task's own from a reset state, so a
                    task stands alone (any order, any loader, any chip)
  unbounded_state   stateful with no bounded warmup: rows replay from the
                    start of the stream/slice group
  device=...        DeviceType.TPU kernels get their inputs staged onto
                    the accelerator once per task column

Usage: python examples/02_op_attributes.py path/to/video.mp4 [db_path]
"""

import struct
import sys
from typing import Any, Sequence

import jax.numpy as jnp
import numpy as np

from scanner_tpu import (CacheMode, Client, DeviceType, FrameType, Kernel,
                         NamedStream, NamedVideoStream, PerfParams,
                         register_op)
import scanner_tpu.kernels  # noqa: F401  (registers BackgroundSubtraction)


@register_op(device=DeviceType.TPU, batch=16)
class BatchBrightness(Kernel):
    """batch: one jitted XLA call per chunk instead of per frame."""

    def execute(self, frame: Sequence[FrameType]) -> Sequence[Any]:
        frames = jnp.asarray(frame, jnp.float32)
        w = jnp.asarray([0.299, 0.587, 0.114])
        return [float(x) for x in (frames * w).sum(-1).mean((1, 2))]


@register_op(device=DeviceType.TPU, stencil=[-1, 0, 1], batch=8)
class TemporalAverage(Kernel):
    """stencil: output row r sees input rows r-1, r, r+1."""

    def execute(self, frame: Sequence[Sequence[FrameType]]
                ) -> Sequence[FrameType]:
        win = jnp.asarray(frame, jnp.float32)  # (batch, 3, H, W, C)
        return jnp.clip(win.mean(axis=1), 0, 255).astype(jnp.uint8)


@register_op(bounded_state=5)
class RunningMax(Kernel):
    """bounded state: a 5-row warmup replays before any requested range,
    so sampling rows [100:110] still sees max over rows >= 95."""

    def __init__(self, config):
        super().__init__(config)
        self.reset()

    def reset(self):
        self.cur = 0.0

    def execute(self, bright: Any) -> bytes:
        self.cur = max(self.cur, float(bright))
        return struct.pack("=d", self.cur)


# Upstream's own bounded-state example in this tutorial is a
# BackgroundSubtraction op (bounded_state=60): a running-average image,
# reset "when the kernel switches to a new part of the stream".  It is
# in the stdlib here (kernels/imgproc.py) as a device op whose average
# lives on the chip between packets; main() runs it beside RunningMax.


@register_op(unbounded_state=True)
class FrameCounter(Kernel):
    """unbounded state: the engine replays from row 0 (or the slice
    start), so the count is exact whatever range was requested."""

    def __init__(self, config):
        super().__init__(config)
        self.reset()

    def reset(self):
        self.n = 0

    def execute(self, ignore: FrameType) -> bytes:
        self.n += 1
        return struct.pack("=q", self.n)


def main():
    video_path = sys.argv[1]
    db_path = sys.argv[2] if len(sys.argv) > 2 else "/tmp/scanner_tpu_db"
    sc = Client(db_path=db_path)
    try:
        movie = NamedVideoStream(sc, "attrs_movie", path=video_path)

        frames = sc.io.Input([movie])
        bright = sc.ops.BatchBrightness(frame=frames)
        smoothed = sc.ops.TemporalAverage(frame=frames)
        rmax = sc.ops.RunningMax(bright=bright)
        count = sc.ops.FrameCounter(ignore=frames)
        moving = sc.ops.BackgroundSubtraction(frame=frames, alpha=0.05,
                                              threshold=0.05)

        outs = [NamedStream(sc, n) for n in
                ("attrs_bright", "attrs_smooth", "attrs_max", "attrs_n",
                 "attrs_moving")]
        sc.run([sc.io.Output(bright, [outs[0]]),
                sc.io.Output(smoothed, [outs[1]]),
                sc.io.Output(rmax, [outs[2]]),
                sc.io.Output(count, [outs[3]]),
                sc.io.Output(moving, [outs[4]])],
               PerfParams.estimate(), cache_mode=CacheMode.Overwrite)

        b = list(outs[0].load())
        m = [struct.unpack("=d", x)[0] for x in outs[2].load()]
        n = [struct.unpack("=q", x)[0] for x in outs[3].load()]
        sm = next(iter(outs[1].load()))
        fg = [struct.unpack("=q", x)[0] for x in outs[4].load()]
        print(f"{len(b)} frames: brightness[0]={b[0]:.1f}, "
              f"running max[-1]={m[-1]:.1f}, count[-1]={n[-1]}, "
              f"smoothed frame shape={sm.shape}, most foreground "
              f"pixels in a frame={max(fg)}")
        assert n[-1] == len(b) == len(fg) and fg[0] == 0
        assert abs(m[-1] - max(b)) < 1e-6
    finally:
        sc.stop()


if __name__ == "__main__":
    main()
