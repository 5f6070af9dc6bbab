"""95th percentile of the window's ``bridge.leg`` spans, ms: how long the
bridge's worker took over one tick's device leg, dispatch and any wait for
the device included. Where the device is the slower side this bounds how
often a tick edge falls."""

from benchmark.lib import program_spans, stats


def read(run):
    legs = program_spans.started_in_window(run, "bridge.leg")
    if not legs:
        return None
    return stats.percentile([(sp[2] - sp[1]) * 1e3 for sp in legs], 95)
