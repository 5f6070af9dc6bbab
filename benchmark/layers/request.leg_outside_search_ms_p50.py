"""Median over the window's requests of the ``bridge.leg`` of the request's
tick less the ``index.search`` spans inside it: the scheduler's stepping and
the response's rows."""

from benchmark.lib.stage_spans import leg_outside_search_ms_p50


def read(run):
    return leg_outside_search_ms_p50(run)
