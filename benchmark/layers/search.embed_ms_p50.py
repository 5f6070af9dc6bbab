"""Median over the window's requests of the program's ``search.embed`` span of
the request's tick: the query's text to its embedding on the host (tokenize,
pack, the encoder's dispatch, the blocking fetch)."""

from benchmark.lib.stage_spans import span_ms_p50


def read(run):
    return span_ms_p50(run, "search.embed")
