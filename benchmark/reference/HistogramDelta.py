"""Plain reference of the shot-detection graph Histogram ->
HistogramDelta: output row i is the L1 distance between the (3, 16)
int32 colour histograms of row i and of row i-1 of the same stream, as a
float with an integer value; row 0 has no predecessor, repeats itself
(REPEAT_EDGE) and reads 0.

The comparison is handed the sampled rows' own wire frames and nothing
of the rows before them, so it finds each row's predecessor inside the
sample: every wire frame names its source row by its barcode, and the
row with index r is compared where the frame with index r-1 stands just
before it, or where r is 0.  The first row of a sampled run that starts
elsewhere goes uncompared, and `delta_uncompared_share` holds that to a
small part of the sample: a comparison that shrinks fails the run."""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

import clipgen
from reference import Histogram, wire

# exact, as the configuration states; and at most one row in twenty of
# the sample without its predecessor (runs of 96 rows leave one in 96)
LIMITS = {"delta_rows_differ": 0, "delta_uncompared_share": 0.05}
CONTROL = Histogram.CONTROL


def make_op_args(cfg, seed, workdir):
    return {}


def delta(prev, cur):
    """L1 distance of two histograms, in integers."""
    return float(np.abs(np.asarray(cur, np.int64)
                        - np.asarray(prev, np.int64)).sum())


def stream_deltas(hists):
    """What a whole stream of histograms, in order, has to read."""
    return [0.0] + [delta(a, b) for a, b in zip(hists, hists[1:])]


def compare(cfg, wire_rows, outputs, control=None, seed=None):
    """`outputs[i]` is what the timed path committed for the frame whose
    wire is `wire_rows[i]`; the sample's runs stand one after the other.
    Returns {name: value} for LIMITS.  With `control` the reference
    itself, its histograms computed in that lower precision, stands in
    the program's place."""
    h, w = cfg["video"]["height"], cfg["video"]["width"]

    def reduce(flat):
        exact = Histogram.expected(flat, h, w)
        return (clipgen.read_barcode(wire.planes(flat, h, w)[0]), exact,
                exact if control is None
                else Histogram.expected(flat, h, w, control))

    # numpy releases the interpreter lock inside its loops
    with ThreadPoolExecutor(Histogram.THREADS) as pool:
        rows = list(pool.map(reduce, wire_rows))
    differ = uncompared = 0
    for i, ((r, exact, low), got) in enumerate(zip(rows, outputs)):
        if r == 0:
            want = lowered = 0.0
        elif i and rows[i - 1][0] == r - 1:
            want = delta(rows[i - 1][1], exact)
            lowered = delta(rows[i - 1][2], low)
        else:
            uncompared += 1
            continue
        if control is not None:
            got = lowered
        got = np.asarray(got)
        differ += got.shape != () or float(got) != want
    return {"delta_rows_differ": differ,
            "delta_uncompared_share": uncompared / max(1, len(rows))}
