"""Upstream's per-detection feature extraction (examples/apps/
open-reid-feature-extraction/extract_features.py; the port
examples/reid_features.py): Input -> the request's sampler ->
ObjectDetect -> the user's Python op TopBox (host) -> CropResize over the
sampled frames and that box -> FaceEmbedding -> the user's Python op
IndexRow (host), which keeps the box beside its vector, the row an index
of who appears where holds.  CropResize takes its two inputs from two
producers, one of them a host op: the device column goes to the host and
the host's column comes back to the device within one task.  TopBox is
registered here as the user's script registers it; each op takes the
configuration's `args` of its entry in `graph.ops`."""

from typing import Any

import numpy as np

from scanner_tpu import register_op
from scanner_tpu.models import unpack_detections

from graphs import chain


@register_op()
def TopBox(config, det: Any) -> Any:
    """Strongest non-degenerate detection's box; the whole frame when
    nothing usable fired (examples/reid_features.py)."""
    d = unpack_detections(det)
    order = np.argsort(d["scores"])[::-1]
    for i in order:
        b = np.asarray(d["boxes"][i], np.float32)
        if (b[2] - b[0]) * (b[3] - b[1]) > 1e-6:
            return b
    return np.asarray([0.0, 0.0, 1.0, 1.0], np.float32)


@register_op()
def IndexRow(config, box: Any, feature: Any) -> Any:
    """[y1, x1, y2, x2, feature...]: where in the frame, and whom."""
    return np.concatenate([np.asarray(box, np.float32),
                           np.asarray(feature, np.float32)])


def build(sc, request, graph, op_args):
    args = {op["op"]: dict(op.get("args", {}), **op_args.get(op["op"], {}))
            for op in graph["ops"]}
    frames = chain.sampled(sc, chain.source(sc, request), request)
    det = sc.ops.ObjectDetect(frame=frames, **args["ObjectDetect"])
    box = sc.ops.TopBox(det=det, **args["TopBox"])
    crops = sc.ops.CropResize(frame=frames, box=box, **args["CropResize"])
    feats = sc.ops.FaceEmbedding(frame=crops, **args["FaceEmbedding"])
    return sc.ops.IndexRow(box=box, feature=feats, **args["IndexRow"])
