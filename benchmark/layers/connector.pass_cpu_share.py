"""Percent of the wall time of the window's ``connector.pass`` spans that the
reader thread was on a CPU (their ``cpu_ms``): near 100 it computes, far
below it waits (the interpreter lock, the disk)."""

from benchmark.lib.stage_spans import pass_cpu_share


def read(run):
    return pass_cpu_share(run)
