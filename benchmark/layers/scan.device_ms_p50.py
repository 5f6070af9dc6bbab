"""Median device time of one execution of the search program, from the
profiler's trace."""

from benchmark.lib.readers import module_p50_ms


def read(run):
    return module_p50_ms(run, ("scan",))
