"""95th percentile of the same latencies as ``query_p50_ms``."""

from benchmark.lib.readers import query_latency_ms


def read(run):
    return query_latency_ms(run, 95.0)
