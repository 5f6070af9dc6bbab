"""Median start-to-start distance of consecutive ``tick`` spans in the
window: the commit loop's period, its sleep plus the tick's own work. A
query waits half of it on average for its tick (``request.queue_ms_p50``)."""

from benchmark.lib.program_spans import tick_period_ms_p50


def read(run):
    return tick_period_ms_p50(run)
