"""The indexers' share of their roofline, percent: the least time for the
visible (query, key) pairs the indexers of the traced window's dispatches
rank (``indexer_cost`` of the cell's architecture over the program's
``embedder.dispatch`` spans' ``tokens`` and ``attn_pairs_indexed``: 2 x 128
flops a visible pair an index head over 32 heads; the index queries, key
and weights read and the choice written once) over the device time of the
fused ingest program under the scope ``decoder.attention.index`` (the
indexer's projections, scores, each query's edge and the mask). None where
the program has no such span field or scope, or the architecture no
``indexer_cost``."""

from benchmark.lib import pair_rooflines


def read(run):
    return pair_rooflines.read(run, "indexer_cost", "attn_pairs_indexed",
                               "decoder.attention.index")
