"""Median ``bridge.wait`` (leg submitted -> leg started on the bridge
worker) of the tick of each of the window's requests."""

from benchmark.lib.program_spans import bridge_wait_ms_p50


def read(run):
    return bridge_wait_ms_p50(run)
