"""Device time of the operations under any of the program's `scopes`
(`jax.named_scope` names) that hold no other operation, summed over the
chips, per row committed in the traced window, in ms.

trace_reduce.reduce_trace's `by_scope` counts every operation under a
scope, and a loop (a `lax.scan`, XLA's while) is an operation whose
interval holds those of its body: a scope with a loop in it reads the
loop's time twice there (PERF.md sec. 7).  Here an operation counts only
where no other operation of its chip starts and ends inside its
interval, and an operation under two of `scopes` counts once.

The harness hands a reducer the reduction's sums alone, so the
operations are read again from the traced run's own file (the
`trace_dir` of `harness.run_cell`, found up the call stack) and kept for
the run's other readers; the two readings of `scopes` are logged to
standard error.  A traced run with no `trace_dir` up the stack raises,
so that a renamed local cannot drop the metrics unseen; no operation
under `scopes` in the window: nothing returned.  (Until the harness
hands the operations in `ctx`: ROADMAP Design 14.)"""

import inspect
import os
import sys

import trace_reduce

_LOADED = {}


def _trace_file():
    frame = inspect.currentframe()
    while frame is not None:
        d = frame.f_locals.get("trace_dir")
        if isinstance(d, str) and os.path.isdir(d):
            return trace_reduce.find_xplane(d)
        frame = frame.f_back
    return None


def _loaded():
    path = _trace_file()
    if path is None:
        raise RuntimeError("a traced run with no `trace_dir` up the call "
                           "stack: the harness no longer names it so")
    if path not in _LOADED:
        _LOADED.clear()
        _LOADED[path] = trace_reduce.load(path)
    return _LOADED[path]


def leaves(evs):
    """The (name, start, end) of one chip's `evs` whose interval holds no
    other's."""
    order = sorted(evs, key=lambda ev: (ev[1], -ev[2]))
    holds = [False] * len(order)
    open_ = []  # indices of the intervals that enclose the current start
    for i, (_, s, e) in enumerate(order):
        while open_ and order[open_[-1]][2] <= s:
            open_.pop()
        if open_ and e <= order[open_[-1]][2]:
            holds[open_[-1]] = True
        open_.append(i)
    return [ev for ev, h in zip(order, holds) if not h]


def leaf_seconds(loaded, scopes):
    """Seconds, summed over the chips, of the window's operations that
    hold no other and lie under one of `scopes`; None where the window
    cannot be placed."""
    want = set(scopes)
    total = 0.0
    for name, evs in loaded["devices"].items():
        marks = loaded["marks"].get(name, [])
        if not evs:
            continue
        if len(marks) < 2:
            return None
        lo, hi = marks[0][1], marks[-1][0]
        under = {op for op, scope in loaded["scopes"].get(name, {}).items()
                 if want & trace_reduce.scope_parts(scope)}
        for op, s, e in leaves(evs):
            if op in under and min(e, hi) > max(s, lo):
                total += (min(e, hi) - max(s, lo)) / 1e9
    return total


def read(ctx, scopes):
    loaded = _loaded() if ctx["trace"] is not None else None
    if loaded is None or not ctx["rows"]:
        return None
    secs = leaf_seconds(loaded, scopes)
    if not secs:
        return None
    every = sum(ctx["trace"].get("by_scope", {}).get(s, 0.0) for s in scopes)
    print(f"[bench] scopes {'+'.join(scopes)}: {secs:.6f} s in operations "
          f"that hold no other, {every:.6f} s in every operation under "
          f"them (by_scope)", file=sys.stderr, flush=True)
    return 1e3 * secs / ctx["rows"]
