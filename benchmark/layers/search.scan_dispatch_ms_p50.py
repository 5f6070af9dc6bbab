"""Median over the window's requests of ``search.scan``'s ``dispatch_ms``: the
time until the jitted search calls returned, before the first fetch."""

from benchmark.lib.stage_spans import span_count_p50


def read(run):
    return span_count_p50(run, "search.scan", "dispatch_ms")
