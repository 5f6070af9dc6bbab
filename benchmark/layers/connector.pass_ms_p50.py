"""Median duration of the ``connector.pass`` spans inside the window: one
listing and ``stat`` of every watched file plus the read of the changed
ones."""

from benchmark.lib.program_spans import pass_ms_p50


def read(run):
    return pass_ms_p50(run)
