"""Percent of the fused ingest program's device time in the traced window
spent in the attention cores of the full-attention layers (the scope
``decoder.attention.full``, inside ``decoder.attention``): what the layers
without a window cost, beside ``ingest.attention_share``, which holds the
projections and the window layers too."""

from benchmark.lib.scope_readers import share


def read(run):
    return share(run, ("decoder.attention.full",))
