"""Percent of the window inside the program's ``index.add_batch`` spans
(tokenize, pack, dispatch): the twin inside the program of
``ingest.add_batch_share``, over the whole window."""

from benchmark.lib.stage_spans import window_share


def read(run):
    return window_share(run, "index.add_batch")
