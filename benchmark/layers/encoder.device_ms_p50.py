"""Median device time of one execution of the plain encoder program (the
query path's), from the profiler's trace."""

from benchmark.lib.readers import module_p50_ms


def read(run):
    return module_p50_ms(run, ("encoder",))
