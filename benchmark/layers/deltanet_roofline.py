"""The chunked delta-rule scan's share of its roofline, percent: the least
time for the shapes dispatched in the traced window (``scan_cost`` of the
cell's architecture: a chunk's products and its triangular solve, the state
read and written once a chunk) over the device time under the scope
``decoder.deltanet.scan`` of the fused ingest program."""

from benchmark.lib.scope_readers import roofline


def read(run):
    return roofline(run, "decoder.deltanet.scan", "scan_cost")
