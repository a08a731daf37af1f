"""Seconds the window's requests spent in program-profiler intervals
named `interval`, summed over every thread, per row committed in the
window, in ms.  No such interval in any request's profile: nothing
returned."""


def read(ctx, interval):
    spans = [iv[2] - iv[1] for req in ctx["requests"]
             for iv in req.get("intervals", ()) if iv[0] == interval]
    if not spans or not ctx["rows"]:
        return None
    return 1e3 * sum(spans) / ctx["rows"]
