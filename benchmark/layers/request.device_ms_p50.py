"""Median of the request tracker's ``device`` stage over the window's
queries: the tick's device leg as the host sees it, from hand-over to
the resolved answer (host stamps, not device time)."""

from benchmark.lib.readers import stage_p50_ms


def read(run):
    return stage_p50_ms(run, "device")
