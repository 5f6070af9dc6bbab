"""Median over the window's requests of the program's ``search.scan`` span of
the request's tick: first search program dispatched -> last result fetched."""

from benchmark.lib.stage_spans import span_ms_p50


def read(run):
    return span_ms_p50(run, "search.scan")
