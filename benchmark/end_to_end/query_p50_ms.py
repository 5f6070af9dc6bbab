"""Median over the window's queries of (HTTP response received - instant
the query was due), at the mix's fixed open-loop rate."""

from benchmark.lib.readers import query_latency_ms


def read(run):
    return query_latency_ms(run, 50.0)
