"""Percent of the fused ingest program's device time in the traced window
spent in the dense feed-forwards (the scope ``decoder.ffn``: two a layer,
each three products of the hidden size on ``ffn_hidden_size``). None where
the program has no such scope."""

from benchmark.lib.scope_readers import share


def read(run):
    return share(run, ("decoder.ffn",))
