"""From a profiler trace (.xplane.pb) to what the benchmark reports: per
device the union of the intervals in which an operation ran, the idle
gaps between them, and the operations that took most time.

Read with `jax.profiler.ProfileData` alone.  A device is a plane named
`/device:TPU:<n>`; its operations are the events of its "XLA Ops" line
(where a plane has no such line, every line but the step and module
summaries counts).  All times are the trace's own nanoseconds.

The window is bounded on each device by the harness's own mark: a tiny
program named MARK that it runs on the idle chip just before the window
opens and just after it closes ("XLA Modules" line).  No host tracer is
needed, and the first mark ties the trace's clock to the host's.
"""

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
SUMMARY_LINES = ("Steps", "XLA Modules", "Framework Ops",
                 "Framework Name Scope", "Source code")
MARK = "jit_benchmark_window_mark"
NAME_CHARS = 160  # of an operation's HLO text, enough to tell it


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _merge(intervals):
    """Union of [start, end) pairs as a sorted list of disjoint pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(merged, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in merged
            if e > lo and s < hi]


def load(path):
    """{"devices": {plane: [(name, start_ns, end_ns), ...]},
        "marks": {plane: [(start_ns, end_ns), ...]}} — `marks` are the
    executions of the MARK program on that device, in time order."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, marks = {}, {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = list(plane.lines)
        ops = [ln for ln in lines if ln.name == "XLA Ops"] or \
            [ln for ln in lines if ln.name not in SUMMARY_LINES]
        evs = []
        for ln in ops:
            for ev in ln.events:
                s = int(ev.start_ns)
                evs.append((ev.name, s, s + int(ev.duration_ns)))
        devices[plane.name] = evs
        marks[plane.name] = sorted(
            (int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns))
            for ln in lines if ln.name == "XLA Modules"
            for ev in ln.events if ev.name.startswith(MARK))
    return {"devices": devices, "marks": marks}


def reduce_trace(loaded, top=10):
    """Each device's window runs from the end of its first mark to the
    start of its last.  Returns None where no device plane holds an
    operation: there is nothing to read.  A device with operations and
    fewer than two marks is an error: the window cannot be placed.

    {"window_s" (mean over devices), "busy_s" (mean over devices),
     "per_device": {name: busy_s}, "device_ops": [[name, seconds], ...]
     (summed over devices, longest first), "gaps": [[start_ns, end_ns],
     ...] (the longest idle gaps of the busiest device's timeline),
     "window_lo_ns" (where that device's window opens)}"""
    devices = {k: v for k, v in loaded["devices"].items() if v}
    if not devices:
        return None
    per_device, by_op, merged_by, bounds = {}, {}, {}, {}
    for name, evs in devices.items():
        marks = loaded["marks"].get(name, [])
        if len(marks) < 2:
            raise ValueError(f"{name}: {len(marks)} window mark(s) in the "
                             f"trace, need the opening and the closing one")
        lo, hi = marks[0][1], marks[-1][0]
        bounds[name] = (lo, hi)
        merged = _clip(_merge([(s, e) for _, s, e in evs]), lo, hi)
        merged_by[name] = merged
        per_device[name] = sum(e - s for s, e in merged) / 1e9
        for op, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                by_op[op] = by_op.get(op, 0) + d
    busiest = max(per_device, key=per_device.get)
    lo, hi = bounds[busiest]
    edges = [lo] + [t for pair in merged_by[busiest] for t in pair] + [hi]
    gaps = sorted(([edges[i], edges[i + 1]]
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]),
                  key=lambda g: g[0] - g[1])[:top]
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": sum(h - l for l, h in bounds.values())
            / 1e9 / len(bounds),
            "busy_s": sum(per_device.values()) / len(per_device),
            "per_device": per_device,
            "device_ops": [[k[:NAME_CHARS], v / 1e9] for k, v in ops],
            "gaps": gaps, "window_lo_ns": lo}


def label_gaps(gaps, window_lo_ns, window_open_host_s, host_intervals,
               order=("evaluate:chunk_wait", "evaluate", "save", "load")):
    """[[label, seconds], ...]: each gap named by the program-profiler
    interval that covers its midpoint on the host ("none" where none
    does; where several do, the first of `order`).  The trace's clock is
    tied to the host's at the window's opening."""
    out = []
    for s, e in gaps:
        mid = window_open_host_s + ((s + e) / 2 - window_lo_ns) / 1e9
        names = {iv[0] for iv in host_intervals if iv[1] <= mid <= iv[2]}
        label = next((n for n in order if n in names), "none")
        out.append([label, (e - s) / 1e9])
    return out
