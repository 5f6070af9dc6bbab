"""Median over the same requests as ``request.device_busy_ms_p50`` of the
``bridge.leg``'s duration less the chip's busy time inside it: host time
inside the device stage (dispatch, Python between programs, the copy of
the answer)."""

from benchmark.lib.program_spans import leg_host_ms_p50


def read(run):
    return leg_host_ms_p50(run)
