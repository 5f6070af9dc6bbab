"""The scan kernel's share of its roofline, percent (the bandwidth bound)."""

from benchmark.lib.readers import scan_roofline


def read(run):
    return scan_roofline(run)
