"""Median of the request tracker's ``host`` stage over the window's
queries: the tick's host leg."""

from benchmark.lib.readers import stage_p50_ms


def read(run):
    return stage_p50_ms(run, "host")
