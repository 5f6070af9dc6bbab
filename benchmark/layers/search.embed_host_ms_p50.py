"""``search.embed`` less the chip's busy time inside it, median over the
requests of the traced part of the window (those ``request.leg_host_ms_p50``
is taken over): the host's part of embedding a query."""

from benchmark.lib.stage_spans import span_host_ms_p50


def read(run):
    return span_host_ms_p50(run, "search.embed")
