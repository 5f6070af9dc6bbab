"""Percent of the window the interpreter spent inside collections of its
garbage collector (every thread of the one-process server waits for each).
With ten million keys live a full collection takes seconds and falls into
few windows (PERF.md, PR 22 finding 8)."""


def read(run):
    pauses = run.extras.get("gc_pauses")
    if pauses is None:
        return None
    return 100.0 * sum(seconds for _t, _gen, seconds in pauses) / run.window_s
