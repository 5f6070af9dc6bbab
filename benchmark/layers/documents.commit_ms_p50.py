"""Median over the live files a ``connector.pass`` pushed in the window of
(commit stamp - push): the end of the ``bridge.leg`` of the first tick
whose ``tick.drain`` started at or after the push, to a tick's precision."""

from benchmark.lib.program_spans import commit_ms_p50


def read(run):
    return commit_ms_p50(run)
