"""Percent of the window spent inside ``tick`` spans: the commit loop at
work, the rest of the window it sleeps."""

from benchmark.lib.program_spans import tick_busy_share


def read(run):
    return tick_busy_share(run)
