"""Median of the request tracker's ``queue`` stage over the window's
queries: the wait for the commit tick that picks the query up."""

from benchmark.lib.readers import stage_p50_ms


def read(run):
    return stage_p50_ms(run, "queue")
