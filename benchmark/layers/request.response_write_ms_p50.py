"""Median of the request tracker's ``response_write`` stage over the window's
queries: resolved answer to the handler's return."""

from benchmark.lib.readers import stage_p50_ms


def read(run):
    return stage_p50_ms(run, "response_write")
