"""Percent of the fused ingest program's device time in the traced window
spent in the expert layers: router, routed experts (their grouped matmuls,
which the compiler names ``ragged-dot-*``, among them), shared expert."""

from benchmark.lib.scope_readers import GROUPED_MATMUL, share


def read(run):
    return share(run, ("decoder.moe.route", "decoder.moe.experts",
                       "decoder.moe.shared"), GROUPED_MATMUL)
