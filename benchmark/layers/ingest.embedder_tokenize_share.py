"""Percent of the window inside the program's ``embedder.tokenize`` spans: the
twin inside the program of ``ingest.tokenizer_share``, over the whole
window."""

from benchmark.lib.stage_spans import window_share


def read(run):
    return window_share(run, "embedder.tokenize")
