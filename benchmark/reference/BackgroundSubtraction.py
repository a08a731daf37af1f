"""Plain reference of the background-subtraction graph
BackgroundSubtraction(frame) under the `All` sampler: output row i of a
stream is `struct.pack("=q", c_i)`, c_i the count of foreground pixels of
table row i, as configs/bgsub_1080p.json states it.

With F_r the float32 of row r's RGB frame (the wire's BT.601 fixed
point, reference/wire.py), `level` = 255 * threshold, float32
throughout, the recurrence started at row s (A = F_s) runs

    c_r = #{pixels with |F_r - A| >= level in every channel}
    A   = A * (1 - alpha) + F_r * alpha          for r = s, s + 1, ...

and row i is c_i of the recurrence started at

    s = max(0, task_rows * floor(i / task_rows) - warmup)

(`graph.task_rows`, `graph.warmup`): a task of `task_rows` output rows
replays its warm-up from a reset state, whatever ran before it.  One
jitted `jax.numpy` step a row on whatever device JAX has, under
`jax.default_matmul_precision("highest")` (there is no matmul in it);
shares no code with the program's kernels.

`bg_count_gap`: the largest |committed c_i - reference c_i| over the
sampled rows, as a share of the frame's pixels (1920 x 1080).
`bg_rows_uncompared`: sampled rows whose history (rows s..i) was not
handed over: the harness hands what `WINDOW` reaches as `window_wires`;
without `rows` the wires are taken as runs by their barcodes.
`bg_shape_errors`: rows that are not 8 bytes.  `bg_first_row_nonzero`:
table rows 0 that do not read 0 (A = F_0 there).
"""

import functools
import struct

import numpy as np

import clipgen
from reference import wire

# warm-up 60 and the 31 rows of a task before its last: what the stated
# configuration reaches back from a sampled row
WINDOW = [-91, 0]
# `bg_count_gap`'s limit lies midway, in ratio, between its two readings
# on the chip (PERF.md sec. 2): the program's largest over its seeds and
# the smaller control's smallest
LIMITS = {"bg_count_gap": 2e-5, "bg_rows_uncompared": 0,
          "bg_shape_errors": 0, "bg_first_row_nonzero": 0}
# "bf16": the nearest precision under the stated float32, the state kept
# in bfloat16 between rows.  "carry": one recurrence from the first row
# handed over (table row 0 where the sample starts there) with no
# restart at a task's start: what a program that lets its state run on
# from task to task would commit.
CONTROL = "bf16"
CONTROLS = ("bf16", "carry")


def make_op_args(cfg, seed, workdir):
    return {}


@functools.lru_cache(maxsize=None)
def stepper(lowered):
    """The jitted step of one row: (A, uint8 RGB, alpha, level) ->
    (A after the row, the row's count); `lowered` keeps A in bfloat16."""
    import jax
    import jax.numpy as jnp

    def kept(a):
        # not astype(bfloat16).astype(float32): the TPU's compiler may
        # keep the excess precision of such a pair and did (the control
        # read 0.0 there); this rounding it has to perform
        return jax.lax.reduce_precision(a, exponent_bits=8,
                                        mantissa_bits=7) if lowered else a

    def step(a, rgb, alpha, level):
        x = rgb.astype(jnp.float32)
        far = jnp.abs(x - a) >= level
        count = jnp.sum(far[..., 0] & far[..., 1] & far[..., 2])
        return kept(a * (jnp.float32(1) - alpha) + x * alpha), count

    jitted = jax.jit(step)

    def run(a, rgb, alpha, level):
        with jax.default_matmul_precision("highest"):
            return jitted(a, rgb, alpha, level)

    def start(rgb):
        return kept(jnp.asarray(rgb).astype(jnp.float32))
    return start, run


def recurrence(rgb_of, first, last, want, alpha, level, lowered=False):
    """{row: count} for the rows of `want`, of the recurrence started at
    row `first` and run through row `last`."""
    start, run = stepper(lowered)
    a, counts = start(rgb_of(first)), {}
    for r in range(first, last + 1):
        a, c = run(a, rgb_of(r), alpha, level)
        if r in want:
            counts[r] = c
    return {r: int(c) for r, c in counts.items()}


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


def task_start(cfg, row):
    g = cfg["graph"]
    t = int(g["task_rows"])
    return max(0, t * (row // t) - int(g["warmup"]))


def committed(got):
    """The count a committed row holds, or None where it is not 8 bytes."""
    raw = got.tobytes() if isinstance(got, np.ndarray) else bytes(got)
    return struct.unpack("=q", raw)[0] if len(raw) == 8 else None


def compare(cfg, wire_rows, outputs, control=None, seed=None, rows=None,
            window_wires=None):
    """`rows[k]` are the source rows of the k-th sampled run, whose
    wires and outputs stand one run after the other; `window_wires[k]`
    maps the rows before them that the run does not hold to their wires.
    `outputs[i]` is what the timed path committed for `wire_rows[i]`.
    With `control` (one of CONTROLS) the reference itself, its state in
    bfloat16 or carried over the tasks' starts, stands in the program's
    place."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"no control {control!r}")
    h, w = cfg["video"]["height"], cfg["video"]["width"]
    args = cfg["graph"]["args"]
    alpha = np.float32(args["alpha"])
    level = np.float32(255.0 * args["threshold"])
    if rows is None:
        rows = runs_by_barcode(cfg, wire_rows)
    if window_wires is None:
        window_wires = [{}] * len(rows)
    gap, uncompared, shape_errors, row0_nonzero, i = 0.0, 0, 0, 0, 0
    for run, halo in zip(rows, window_wires):
        flat = dict(zip(run, wire_rows[i:i + len(run)]))
        flat.update(halo)
        got_rows = outputs[i:i + len(run)]
        i += len(run)
        rgb = {}

        def rgb_of(r):
            if r not in rgb:
                rgb[r] = wire.to_rgb(flat[r], h, w)
            return rgb[r]

        # the stated answer, a task at a time; a task whose history is
        # not all here leaves its rows uncompared
        want, tasks = {}, {}
        for r in run:
            tasks.setdefault(task_start(cfg, r), []).append(r)
        for s, members in tasks.items():
            if all(r in flat for r in range(s, max(members) + 1)):
                want.update(recurrence(rgb_of, s, max(members),
                                       set(members), alpha, level))
        have = None
        if control == "bf16":
            have = {}
            for s, members in tasks.items():
                if members[0] in want:
                    have.update(recurrence(rgb_of, s, max(members),
                                           set(members), alpha, level,
                                           lowered=True))
        elif control == "carry":
            first = min(run)
            while first - 1 in flat:
                first -= 1
            have = recurrence(rgb_of, first, max(run), set(run), alpha,
                              level)
        for r, got in zip(run, got_rows):
            if r not in want:
                uncompared += 1
                continue
            count = have[r] if have is not None else committed(got)
            if count is None:
                shape_errors += 1
                continue
            if r == 0:
                row0_nonzero += int(count != 0)
            gap = max(gap, abs(count - want[r]) / float(h * w))
    return {"bg_count_gap": gap, "bg_rows_uncompared": uncompared,
            "bg_shape_errors": shape_errors,
            "bg_first_row_nonzero": row0_nonzero}
