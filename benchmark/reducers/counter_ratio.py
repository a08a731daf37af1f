"""Ratio of two counts taken over the measured window.  `num` and `den`
each name a counter series of the program (`series`, optional `labels`
that must match and `not_labels` that must not) or the word "rows" (rows
committed in the window).  Nothing to divide by: nothing returned."""


def delta(ctx, spec):
    if isinstance(spec, dict) and "sum" in spec:
        return sum(ctx["counter_delta"](s, None, None) for s in spec["sum"])
    if spec == "rows":
        return float(ctx["rows"])
    return ctx["counter_delta"](spec["series"], spec.get("labels"),
                                spec.get("not_labels"))


def read(ctx, num, den, scale=1.0):
    d = delta(ctx, den)
    if d <= 0:
        return None
    return scale * delta(ctx, num) / d
