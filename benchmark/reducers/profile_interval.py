"""Time from each request's `Client.run` call to the start of the first
program-profiler interval named `interval` in that job's profile; the
median over the window's requests, in ms."""

import statistics


def read(ctx, interval):
    waits = []
    for req in ctx["requests"]:
        starts = [iv[1] for iv in req["intervals"] if iv[0] == interval]
        if starts:
            waits.append(min(starts) - req["t_call"])
    if not waits:
        return None
    return 1e3 * statistics.median(waits)
