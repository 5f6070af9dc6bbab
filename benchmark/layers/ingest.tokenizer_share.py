"""Percent of the traced window's wall time the host spent inside
the tokenizer, from the benchmark's span around the call."""

from benchmark.lib.readers import span_share


def read(run):
    return span_share(run, "tokenizer.batch")
