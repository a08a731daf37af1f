"""Upstream's walkthrough (examples/apps/walkthroughs/
grayscale_conversion.py; the port examples/grayscale_conversion.py):
Input -> the request's sampler -> the configuration's ops in turn, the
last of them the user's own Python op, registered here as the user's
script registers it.  An op's `stream_args` are handed per stream
(`Resize(width=[640, ...], height=[480, ...])`, as upstream's Resize
takes them), its `args` as they stand."""

import numpy as np

from scanner_tpu import FrameType, register_op

from graphs import chain


@register_op()
def CloneChannels(config, frame: FrameType, replications=3) -> FrameType:
    """Replicate a (possibly single-channel) frame into N channels: the
    walkthrough's custom-op teaching point."""
    f = np.asarray(frame)
    if f.ndim == 3:
        f = f[..., 0]
    return np.dstack([f] * replications)


def build(sc, request, graph, op_args):
    node = chain.sampled(sc, chain.source(sc, request), request)
    for op in graph["ops"]:
        per_stream = {k: [v] * len(request)
                      for k, v in op.get("stream_args", {}).items()}
        node = getattr(sc.ops, op["op"])(
            frame=node, **op.get("args", {}), **per_stream,
            **op_args.get(op["op"], {}))
    return node
