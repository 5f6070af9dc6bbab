"""Percent of the fused ingest program's device time in the traced window
spent in the Gated DeltaNet mixers: projections, convolution, chunked
scan."""

from benchmark.lib.scope_readers import share


def read(run):
    return share(run, ("decoder.deltanet",))
