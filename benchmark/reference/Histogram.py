"""Plain reference of the Histogram op: per colour channel, 16 bins of
16 levels over the frame's RGB, as int32 (3, 16)."""

import numpy as np

from reference import wire

# what the configuration states: integer-exact
LIMITS = {"hist_rows_differ": 0}
THREADS = 8


def expected(flat, h, w, dtype=np.int32):
    rgb = wire.to_rgb(flat, h, w, dtype)
    return np.stack([np.bincount(rgb[..., c].ravel() >> 4, minlength=16)
                     for c in range(3)]).astype(np.int32)


def make_op_args(cfg, seed, workdir):
    return {}


def compare(cfg, wire_rows, outputs, control=None, seed=None):
    """`outputs[i]` is what the timed path committed for the frame whose
    wire is `wire_rows[i]`.  Returns {name: value} for LIMITS.  With
    `control` the reference itself, computed in that lower precision,
    stands in the program's place."""
    from concurrent.futures import ThreadPoolExecutor
    h, w = cfg["video"]["height"], cfg["video"]["width"]

    def differs(pair):
        flat, got = pair
        want = expected(flat, h, w)
        if control is not None:
            got = expected(flat, h, w, control)
        got = np.asarray(got)
        return got.shape != want.shape or not np.array_equal(got, want)

    # numpy releases the interpreter lock inside its loops
    with ThreadPoolExecutor(THREADS) as pool:
        return {"hist_rows_differ":
                sum(pool.map(differs, zip(wire_rows, outputs)))}


import ml_dtypes  # noqa: E402 — ships with jax

# the conversion in bfloat16 floating point: the step a later PR would be
# tempted to take on the device
CONTROL = ml_dtypes.bfloat16
