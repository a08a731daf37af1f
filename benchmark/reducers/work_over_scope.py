"""Least time the chip could take for the rows of the traced window (the
work function's count over the chip's peak) as a share of the device
time of one `jax.named_scope` of the program (`scope`): a kernel's
roofline share.  The scope's time is that of its operations that hold
no other (leaf_scope_ms_per_row), summed over the chips.  `work` names a
file under work/, `count` the key of its result, `peak` a column of
peaks.json.  No operation under the scope: nothing returned.  Over 100 %
the count or the scope's time is wrong: the share is returned as read,
and logged."""

import importlib
import sys

from reducers import leaf_scope_ms_per_row as leaf


def read(ctx, work, count, peak, scope):
    loaded = leaf._loaded() if ctx["trace"] is not None else None
    if loaded is None or not ctx["rows"]:
        return None
    secs = leaf.leaf_seconds(loaded, [scope])
    if not secs:
        return None
    amount = importlib.import_module("work." + work) \
        .work(ctx["cfg"], ctx["rows"])[count]
    share = 100.0 * amount / ctx["peaks"][peak] / secs
    if share > 100.0:
        print(f"[bench] {work}/{peak} over {scope}: {share:.1f} % of peak "
              f"in {secs:.6f} s: the count or the scope's time is wrong",
              file=sys.stderr, flush=True)
    return share
