"""Percent of the fused ingest program's device time in the traced window
spent in the indexers (the scope ``decoder.attention.index``, inside
``decoder.attention``): the index queries', key's and weights' projections,
the scores of every visible pair, each query's edge and the mask of the
choice, in the layers that hold an indexer. None where the program has no
such scope."""

from benchmark.lib.scope_readers import share


def read(run):
    return share(run, ("decoder.attention.index",))
