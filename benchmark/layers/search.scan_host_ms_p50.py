"""``search.scan`` less the chip's busy time inside it, median over the
requests of the traced part of the window: the host's part of a scan (the
dispatch, the upload still in flight, the two fetches)."""

from benchmark.lib.stage_spans import span_host_ms_p50


def read(run):
    return span_host_ms_p50(run, "search.scan")
