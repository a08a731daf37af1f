"""A count less its parts, over another: what a stage's self time is
read through (a layer's own time is its span's less what its child
spans cover).  `whole`, each of `parts` and `den` are specs as
counter_ratio's (a series with optional labels, a {"sum": [...]} of
series, or the word "rows").

Unlike counter_sums, a part that counted nothing in the window takes
nothing away: a cell that never decodes has no decode seconds, and its
load stage still has a self time.  So the program that lacks the parts
altogether (the parent of the PR that added them) would report the
whole under the name of what is left of it; `witness` names a series
that came with the parts and counts in every window whose rows passed
through the stage: where it counted nothing, nothing is returned.
With no `parts` the reducer is a ratio that reads 0 where the program
has the series and nothing fired, and nothing where it has not.
Nothing to divide by: nothing returned."""

from reducers import counter_ratio


def read(ctx, whole, witness, den, parts=(), scale=1.0):
    d = counter_ratio.delta(ctx, den)
    if d <= 0 or counter_ratio.delta(ctx, witness) <= 0:
        return None
    left = counter_ratio.delta(ctx, whole) \
        - sum(counter_ratio.delta(ctx, p) for p in parts)
    return scale * left / d
