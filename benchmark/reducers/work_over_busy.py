"""Least time the chip could take for the rows of the traced window (the
work function's count over the chip's peak) as a share of the time its
devices were busy in that window, whatever programs ran.  `work` names a
file under work/, `count` the key of its result, `peak` a column of
peaks.json.  Over 100 % the count is wrong: the run fails."""

import importlib


def read(ctx, work, count, peak):
    tr = ctx["trace"]
    if tr is None or not ctx["rows"]:
        return None
    amount = importlib.import_module("work." + work) \
        .work(ctx["cfg"], ctx["rows"])[count]
    busy = sum(tr["per_device"].values())
    share = 100.0 * amount / ctx["peaks"][peak] / busy
    if share > 100.0:
        raise RuntimeError(
            f"{work}/{peak}: {share:.1f} % of peak over {busy:.3f} s busy "
            f"— the count or the busy time is wrong")
    return share
