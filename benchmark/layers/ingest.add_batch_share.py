"""Percent of the traced window's wall time the host spent inside
``index.add_batch`` (tokenize, pack, dispatch), from the benchmark's span around the call."""

from benchmark.lib.readers import span_share


def read(run):
    return span_share(run, "index.add_batch")
