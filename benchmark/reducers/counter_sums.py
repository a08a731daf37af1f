"""Ratio of two signed sums of counts taken over the measured window:
what the `save` stage's metrics are read through, all of them, so that
one rule holds for the stage.  `num` and `den` are lists of [sign, spec]
terms, each `spec` as counter_ratio's (a series with optional labels,
or the word "rows"); one series cannot say a stage's seconds less the
parts that have counters of their own, or a share of a whole that is
itself a sum.

A series that counted nothing in a window whose rows passed through the
stage it belongs to is a series the program does not have (the parent
of the PR that added it): nothing returned, where counter_ratio would
report a 0 that reads as "no cost", or the whole under a part's name.
The same silence would hide a counter that broke, which is why
tests/test_frame_cells_cpu.py holds every one of these metrics above 0
in `blur_dense`'s traced run.  Nothing to divide by: nothing returned."""

from reducers import counter_ratio


def total(ctx, terms):
    counts = [(sign, spec, counter_ratio.delta(ctx, spec))
              for sign, spec in terms]
    if any(spec != "rows" and n <= 0 for _, spec, n in counts):
        return None
    return sum(sign * n for sign, _, n in counts)


def read(ctx, num, den, scale=1.0):
    n, d = total(ctx, num), total(ctx, den)
    if n is None or d is None or d <= 0:
        return None
    return scale * n / d
