"""Share of the devices' busy time that lies under none of the
program's top-level scopes: 100 x (1 - the seconds of the operations
under the `scopes` / the busy seconds), both summed over the chips
(trace_reduce.reduce_trace's `by_scope` and `per_device`).

`scopes` lists the outermost `jax.named_scope` of each of the program's
device programs and no scope that lies inside another of the list: an
operation counts under every name on its path, so a nested pair would
count it twice.  Operations that overlap in time on one chip (a copy
under a kernel) count each for itself above and once below, so the
share can read a little under 0; it is not cut off there.  A trace
without scopes (a compile cache older than the scopes): nothing
returned."""


def read(ctx, scopes):
    tr = ctx["trace"]
    if tr is None or not tr.get("by_scope"):
        return None
    busy = sum(tr["per_device"].values())
    if busy <= 0:
        return None
    named = sum(tr["by_scope"].get(s, 0.0) for s in scopes)
    return 100.0 * (1.0 - named / busy)
