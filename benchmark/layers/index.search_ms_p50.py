"""Median host-clocked time of one ``index.search`` call in the window (the
benchmark's span around it: query encode, scan, read-back, merge)."""

from benchmark.lib.readers import span_p50_ms


def read(run):
    return span_p50_ms(run, "index.search")
