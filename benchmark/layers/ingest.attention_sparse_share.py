"""Percent of the fused ingest program's device time in the traced window
spent in the attention cores over a learned choice of keys (the scope
``decoder.attention.sparse``, inside ``decoder.attention``): beside
``ingest.attention_index_share``, the indexers that make the choice,
``ingest.attention_latent_share``, the low-rank projections, and
``ingest.attention_share``, which holds all three and the output
projection. None where the program has no such scope."""

from benchmark.lib.scope_readers import share


def read(run):
    return share(run, ("decoder.attention.sparse",))
