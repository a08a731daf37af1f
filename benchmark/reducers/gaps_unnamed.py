"""Share of the device's longest idle gaps that the program cannot
name: of the seconds in the trace's `gaps` (trace_reduce.reduce_trace:
the ten longest of the busiest chip), the part in gaps whose midpoint
lies in no program-profiler interval other than the `containers`, in %.

A gap's host time is reckoned as trace_reduce.label_gaps does, the
trace's clock tied to the host's where the window opens; the first
finished request's `t_call` stands for that moment (the harness opens
the window one graph build, some ms, before it; the gaps are tenths of
a second)."""


def read(ctx, containers):
    tr = ctx["trace"]
    if tr is None or not tr.get("gaps") or not ctx["requests"]:
        return None
    t_open = min(r["t_call"] for r in ctx["requests"])
    named = [(iv[1], iv[2]) for r in ctx["requests"]
             for iv in r.get("intervals", ()) if iv[0] not in containers]
    total = bare = 0.0
    for s, e in tr["gaps"]:
        mid = t_open + ((s + e) / 2 - tr["window_lo_ns"]) / 1e9
        total += (e - s) / 1e9
        if not any(lo <= mid <= hi for lo, hi in named):
            bare += (e - s) / 1e9
    if total <= 0:
        return None
    return 100.0 * bare / total
