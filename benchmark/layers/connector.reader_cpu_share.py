"""Percent of the wall time of the window's ``connector.progress`` spans that
the reader thread was on a CPU (their ``cpu_ms``): near 100 a second reader
in the same interpreter buys nothing, far below it the thread waits."""

from benchmark.lib.stage_spans import progress_share


def read(run):
    return progress_share(run, "cpu_ms")
