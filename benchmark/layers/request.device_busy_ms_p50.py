"""Median over the requests of the traced part of the window of the time
the chip was busy inside the ``bridge.leg`` of the request's tick: the
device time spent for a served request, from the profile's operation
intervals put on the program's clock."""

from benchmark.lib.program_spans import device_busy_ms_p50


def read(run):
    return device_busy_ms_p50(run)
