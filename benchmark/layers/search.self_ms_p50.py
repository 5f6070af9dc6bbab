"""Median over the window's requests of ``index.search`` less the
``search.embed`` and ``search.scan`` inside it: the flush, the stack and the
upload of the query matrix, the ranking (its counts are logged beside it)."""

from benchmark.lib.stage_spans import search_self_ms_p50


def read(run):
    return search_self_ms_p50(run)
