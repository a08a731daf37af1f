"""The one traffic generator: from a traffic file's parameters, a
configuration and the seed to the tables to ingest, the set-up requests
and the window's requests.  Closed loop, one client: the harness sends
the next request when the last has returned.

A traffic file (traffic/<name>.json) holds

  tables            the window's corpus, in tables of the configuration's clip
  resident_tables   OTHER tables that set-up scans first, all rows: the
                    work that went before in a long-running deployment.
                    They leave the frame cache full of pages the window
                    never asks for, so the corpus is cold on every pass
                    whatever the cache's policy.  0 for none
  fill_corpus       whether set-up also scans the corpus itself, all
                    rows (a hot set that the window then finds cached)
  fill_bulk_tables  tables per set-up scan request
  streams           tables per window request (one output stream each)
  shapes            what a request asks of each of its tables, one of:
                    {"sampler": "All"}
                    {"sampler": "Range", "count": rows}
                    {"sampler": "StridedRange", "count": rows, "stride": k}
                    {"sampler": "Stride", "stride": k}      (whole table)
                    {"sampler": "Gather", "count": rows, "stride": k}
                    `sampler` is the name of the program's stream op
                    (`sc.streams.<sampler>`); the first row is dealt out
                    by the seed, a multiple of the video's keyint
  per_chip          multiply the four table counts by the cell's chips
                    (each chip's cache keys are its own)
  check             {"streams": n, "rows": m}: how much of the window
                    the comparison samples (harness.check_sample)

The window's requests come in blocks that hold every shape once, in a
seeded order, over the corpus's tables dealt out `streams` at a time in
a seeded cyclic order: wherever the window ends, every seed has sent the
same mix of shapes to within one block, and a corpus larger than the
cache is scanned cyclically.  Set-up sends the scans, then one request of
each shape that no scan already has (so every shape is compiled).

A request is a list of streams {"table", "sampler", "rows"}: `rows` is
the `range` of source rows the stream's output holds, in order.
"""

import numpy as np


def sampler_args(stream):
    """What `sc.streams.<sampler>` takes for this stream."""
    r = stream["rows"]
    return {"Range": (r.start, r.stop),
            "StridedRange": (r.start, r.stop, r.step),
            "Stride": r.step,
            "Gather": list(r)}[stream["sampler"]]


def plan(traffic, cfg, seed, chips):
    mult = chips if traffic.get("per_chip") else 1
    n, resident, fill_per, per = (
        traffic[k] * mult for k in
        ("tables", "resident_tables", "fill_bulk_tables", "streams"))
    if n % per or resident % fill_per \
            or (traffic["fill_corpus"] and n % fill_per):
        raise ValueError("tables are not a whole number of requests")
    frames, keyint = cfg["video"]["frames"], cfg["video"]["keyint"]
    shapes = traffic["shapes"]
    rng = np.random.default_rng([int(seed), 1])
    tables = [f"clip_{i:03d}" for i in range(n)]
    earlier = [f"earlier_{i:03d}" for i in range(resident)]
    order = [tables[i] for i in rng.permutation(n)]

    def stream(table, shape):
        stride, count = shape.get("stride", 1), shape.get("count")
        if count is None:  # the whole table from its first row
            rows = range(0, frames, stride)
        else:
            slots = (frames - 1 - (count - 1) * stride) // keyint + 1
            if slots < 1:
                raise ValueError(f"{shape} does not fit {frames} frames")
            start = keyint * int(rng.integers(slots))
            rows = range(start, start + count * stride, stride)
        return {"table": table, "sampler": shape["sampler"], "rows": rows}

    def scans(names):
        return [[stream(t, {"sampler": "All"}) for t in names[s:s + fill_per]]
                for s in range(0, len(names), fill_per)]

    def requests():
        k = 0
        while True:
            for i in rng.permutation(len(shapes)):
                yield [stream(order[(k + j) % n], shapes[i])
                       for j in range(per)]
                k += per

    warm = scans(earlier) + (scans(tables) if traffic["fill_corpus"] else [])
    scanned = warm and per == fill_per
    warm += [[stream(t, shape) for t in tables[:per]] for shape in shapes
             if not (scanned and shape["sampler"] == "All")]
    return {"tables": tables + earlier, "warm": warm, "requests": requests()}
