"""The sparse attention cores' share of their roofline, percent: the least
time for the chosen (query, key) pairs of the traced window's dispatches
(``sparse_attention_cost`` of the cell's architecture over the program's
``embedder.dispatch`` spans' ``tokens`` and ``attn_pairs_selected``:
2 x (256 + 256) flops a chosen pair a head; q, k, v and o once a layer in
bfloat16) over the device time of the fused ingest program under the scope
``decoder.attention.sparse``. Pairs a lowering computes and masks are not
counted, so the share stays under 100. None where the program has no such
span field or scope, or the architecture no ``sparse_attention_cost``."""

from benchmark.lib import pair_rooflines


def read(run):
    return pair_rooflines.read(run, "sparse_attention_cost", "attn_pairs_selected",
                               "decoder.attention.sparse")
