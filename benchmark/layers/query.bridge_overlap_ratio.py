"""Device legs that overlapped host work over legs resolved in the window
(``Scheduler.bridge_stats()`` after - before)."""

from benchmark.lib.readers import bridge_overlap_ratio


def read(run):
    return bridge_overlap_ratio(run)
