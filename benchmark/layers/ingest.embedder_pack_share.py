"""Percent of the window inside the program's ``embedder.pack`` spans less
their ``embedder.tokenize``: the packer's own time."""

from benchmark.lib.stage_spans import window_share


def read(run):
    return window_share(run, "embedder.pack", less="embedder.tokenize")
