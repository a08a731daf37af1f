"""Image encode/decode kernels.

Capability parity: reference util/image_encoder.cpp (lodepng/jpeg encode)
and the scannertools image ops.  PIL handles the codecs; these are host
(CPU) ops by nature.  (`Grayscale` is a device op: kernels/imgproc.py.)
"""

from __future__ import annotations

import io

import numpy as np

from ..common import FrameType
from ..graph.ops import Kernel, register_op


@register_op()
class ImageEncode(Kernel):
    """frame -> encoded image bytes (png/jpeg/webp)."""

    def __init__(self, config, format: str = "png", quality: int = 90):
        super().__init__(config)
        self.format = format.upper()
        self.quality = int(quality)

    def execute(self, frame: FrameType) -> bytes:
        from PIL import Image
        img = Image.fromarray(np.asarray(frame))
        buf = io.BytesIO()
        kw = {"quality": self.quality} if self.format in ("JPEG",) else {}
        img.save(buf, format=self.format, **kw)
        return buf.getvalue()


@register_op()
class ImageDecode(Kernel):
    """encoded image bytes -> RGB frame."""

    def execute(self, data: bytes) -> FrameType:
        from ..video.ingest import decode_image
        return decode_image(data)
