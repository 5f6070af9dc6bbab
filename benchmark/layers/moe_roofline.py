"""The routed experts' grouped products' share of their roofline, percent:
the least time for the shapes dispatched in the traced window
(``experts_cost`` of the cell's architecture: the expected held experts a
token, every held expert's weights read once a dispatch) over the device
time of the fused ingest program under the scope ``decoder.moe.experts``
(sort, gather, combine) and in the compiler's grouped matmuls
(``ragged-dot-*``: the three products themselves, which carry no scope)."""

from benchmark.lib.scope_readers import GROUPED_MATMUL, roofline


def read(run):
    return roofline(run, "decoder.moe.experts", "experts_cost",
                    GROUPED_MATMUL)
