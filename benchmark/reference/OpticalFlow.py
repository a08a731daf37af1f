"""Plain reference of the optical-flow graph Range(OpticalFlow(frame)):
output row i of a stream is the dense flow from source row rows[i] - 1
to source row rows[i] of its TABLE (the sampler sits after the op, so
the window lies over the table, not over the sampled rows), a
(h, w, 2) float32 field (u, v); table row 0 has no predecessor, repeats
itself (REPEAT_EDGE) and reads exactly 0.

The equations, as configs/flow_1080p.json states them, float32
throughout.  With E the luma 0.299 R + 0.587 G + 0.114 B of the wire's
BT.601 RGB (reference/wire.py), P the previous frame and N the current:

    Ix = (P[y, x+1] - P[y, x-1]) / 2,  Iy = (P[y+1, x] - P[y-1, x]) / 2
         (indices wrap at the borders),  It = N - P
    u = v = 0;  16 times:
        ub, vb = A(u), A(v)
        t = (Ix ub + Iy vb + It) / (15^2 + Ix^2 + Iy^2)
        u, v = ub - Ix t, vb - Iy t

where A is the mean of the 3x3 neighbourhood (edge neighbours 1/6,
corners 1/12, centre 0) of the field with its border replicated.  Plain
`jax.numpy` on whatever device JAX has, under
`jax.default_matmul_precision("highest")`, one pair of frames a call;
shares no code with the program's kernels.

`flow_gap`: the largest |committed - reference| of a row as a share of
max(1, largest |reference| of that row), the worst row of the sample.
`flow_rows_uncompared`: sampled rows whose predecessor's wire was not
handed over (the harness hands it as `window_wires`; without `rows` the
wires are taken as runs by their barcodes, and each run's first row
that is not table row 0 goes uncompared).  `flow_shape_errors`: rows not
(h, w, 2) float32.  `flow_row0_nonzero`: table rows 0 that do not read 0.
"""

import functools

import numpy as np

import clipgen
from reference import wire

WINDOW = [-1, 0]
# `flow_gap`'s limit lies midway, in ratio, between its two readings on
# the chip (PERF.md sec. 2): the program, float32 sums in another order,
# 1.3e-7 to 2.4e-7; the control 8.4e-3 to 8.8e-3 (the parent's op, a
# default-precision convolution, 1.2e-2)
LIMITS = {"flow_gap": 5e-5, "flow_rows_uncompared": 0,
          "flow_shape_errors": 0, "flow_row0_nonzero": 0}
# the nearest precision under the stated float32: the operands of the
# neighbourhood average (field and weights) rounded to bfloat16, summed
# in float32: what a default-precision convolution does on the chip
CONTROL = "bf16"
ITERS, ALPHA = 16, 15.0
LUMA = (0.299, 0.587, 0.114)
# (dy, dx, weight) of the neighbourhood average
TAPS = tuple((dy, dx, 1 / 6 if 0 in (dy, dx) else 1 / 12)
             for dy in (-1, 0, 1) for dx in (-1, 0, 1) if (dy, dx) != (0, 0))


def make_op_args(cfg, seed, workdir):
    return {}


@functools.lru_cache(maxsize=None)
def solver(control):
    """The jitted flow of one frame pair: (h, w, 3) uint8 RGB twice ->
    (h, w, 2) float32."""
    import jax
    import jax.numpy as jnp

    def luma(rgb):
        f = rgb.astype(jnp.float32)
        return (jnp.float32(LUMA[0]) * f[..., 0]
                + jnp.float32(LUMA[1]) * f[..., 1]
                + jnp.float32(LUMA[2]) * f[..., 2])

    def lowered(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32) \
            if control == "bf16" else x

    def average(x):
        h, w = x.shape
        p = lowered(jnp.pad(x, 1, mode="edge"))
        out = jnp.zeros_like(x)
        for dy, dx, weight in TAPS:
            out = out + lowered(jnp.float32(weight)) \
                * p[1 + dy:1 + dy + h, 1 + dx:1 + dx + w]
        return out

    def flow(prev_rgb, next_rgb):
        prev, nxt = luma(prev_rgb), luma(next_rgb)
        ix = (jnp.roll(prev, -1, 1) - jnp.roll(prev, 1, 1)) * jnp.float32(0.5)
        iy = (jnp.roll(prev, -1, 0) - jnp.roll(prev, 1, 0)) * jnp.float32(0.5)
        it = nxt - prev
        denom = jnp.float32(ALPHA * ALPHA) + ix * ix + iy * iy
        u, v = jnp.zeros_like(prev), jnp.zeros_like(prev)
        for _ in range(ITERS):
            ub, vb = average(u), average(v)
            t = (ix * ub + iy * vb + it) / denom
            u, v = ub - ix * t, vb - iy * t
        return jnp.stack([u, v], -1)

    jitted = jax.jit(flow)

    def run(prev_rgb, next_rgb):
        with jax.default_matmul_precision("highest"):
            return np.asarray(jitted(prev_rgb, next_rgb))
    return run


def runs_by_barcode(cfg, wire_rows):
    """Without `rows`: the source row of each wire read off its barcode,
    and the wires cut into runs of consecutive rows."""
    h, w = cfg["video"]["height"], cfg["video"]["width"]
    runs = []
    for flat in wire_rows:
        row = clipgen.read_barcode(wire.planes(flat, h, w)[0])
        if runs and runs[-1][-1] == row - 1:
            runs[-1].append(row)
        else:
            runs.append([row])
    return runs


def compare(cfg, wire_rows, outputs, control=None, seed=None, rows=None,
            window_wires=None):
    """`rows[k]` are the source rows of the k-th sampled run, whose
    wires and outputs stand one run after the other; `window_wires[k]`
    maps the rows before them that the run does not hold to their wires.
    `outputs[i]` is what the timed path committed for `wire_rows[i]`.
    With `control` the reference itself, its average in that lower
    precision, stands in the program's place."""
    h, w = cfg["video"]["height"], cfg["video"]["width"]
    if rows is None:
        rows = runs_by_barcode(cfg, wire_rows)
    if window_wires is None:
        window_wires = [{}] * len(rows)
    exact = solver(None)
    gap, uncompared, shape_errors, row0_nonzero, i = 0.0, 0, 0, 0, 0
    for run, halo in zip(rows, window_wires):
        rgb = {r: wire.to_rgb(f, h, w)
               for r, f in zip(run, wire_rows[i:i + len(run)])}
        rgb.update({r: wire.to_rgb(f, h, w) for r, f in halo.items()})
        for row in run:
            got, i = outputs[i], i + 1
            prev = rgb.get(max(row - 1, 0))
            if prev is None:
                uncompared += 1
                continue
            want = exact(prev, rgb[row])
            if control is not None:
                got = solver(control)(prev, rgb[row])
            got = np.asarray(got)
            if got.shape != (h, w, 2) or got.dtype != np.float32:
                shape_errors += 1
                continue
            if row == 0:
                row0_nonzero += int(np.any(got != 0))
            gap = max(gap, float(np.abs(got - want).max()
                                 / max(1.0, np.abs(want).max())))
    return {"flow_gap": gap, "flow_rows_uncompared": uncompared,
            "flow_shape_errors": shape_errors,
            "flow_row0_nonzero": row0_nonzero}
