"""Share of the window's `Client.run` time that no program-profiler
interval names: over the finished requests, the part of each request's
call -> return that lies in no interval of its job other than the
`containers` (spans that hold a whole run or all its stage threads, and
so name nothing), summed, over the summed call -> return, in %."""


def uncovered(lo, hi, intervals):
    """Seconds of [lo, hi] outside the union of (start, end) pairs."""
    covered, edge = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, edge), min(e, hi)
        if e > s:
            covered += e - s
            edge = e
    return (hi - lo) - covered


def read(ctx, containers):
    total = bare = 0.0
    for req in ctx["requests"]:
        named = [(iv[1], iv[2]) for iv in req.get("intervals", ())
                 if iv[0] not in containers]
        total += req["t_done"] - req["t_call"]
        bare += uncovered(req["t_call"], req["t_done"], named)
    if total <= 0:
        return None
    return 100.0 * bare / total
