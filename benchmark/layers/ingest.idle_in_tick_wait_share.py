"""Percent of the idlest chip's idle time in the traced part of the window
that lies outside every ``tick`` and ``bridge.leg`` span: the chip idle
while the commit loop slept, not while the host worked."""

from benchmark.lib.program_spans import idle_in_tick_wait_share


def read(run):
    return idle_in_tick_wait_share(run)
