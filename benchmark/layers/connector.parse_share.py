"""Percent of the wall time of the window's ``connector.progress`` spans spent
reading, decoding and keying files (their ``parse_ms``; the ``stat`` and push
shares are logged beside it)."""

from benchmark.lib.stage_spans import progress_share


def read(run):
    return progress_share(run, "parse_ms")
