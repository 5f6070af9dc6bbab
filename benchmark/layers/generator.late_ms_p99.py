"""99th percentile of (sent - due) over the window's queries: a starved
generator must not be read as a fast server."""

from benchmark.lib import stats


def read(run):
    late = [(r.sent - r.due) * 1e3 for r in run.window_queries()]
    return stats.percentile(late, 99.0) if late else None
