"""Median duration of the window's ``tick`` spans that picked up a request:
the loop awake, from its wake-up to ``run_time``'s return with the device
leg submitted."""

from benchmark.lib.program_spans import tick_busy_ms_p50


def read(run):
    return tick_busy_ms_p50(run)
