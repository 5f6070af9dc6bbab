"""Documents that became retrievable in the window over its length, counted
from ``/v1/statistics`` ``file_count`` at the window's edges (each taken on
a tick edge; the runner holds the count against the index's rows)."""


def read(run):
    if "file_count" not in run.before:
        return None
    return (run.after["file_count"] - run.before["file_count"]) / run.window_s
