"""(``tick`` + ``bridge.leg`` ms per drained row in the window's last
third) over (the same in its first third): whether the backlog's decaying
rate is inside the ticks (well over 1) or outside them (about 1)."""

from benchmark.lib.program_spans import tick_ms_per_row_drift


def read(run):
    return tick_ms_per_row_drift(run)
