"""Percent of the traced window's wall time the host spent inside
the embedder's packer (tokenizer included), from the benchmark's span around the call."""

from benchmark.lib.readers import span_share


def read(run):
    return span_share(run, "pack")
