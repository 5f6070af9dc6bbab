"""Wall time of the window's ``connector.progress`` spans over the files they
read: what one file of a backlog costs the reader."""

from benchmark.lib.stage_spans import file_ms_mean


def read(run):
    return file_ms_mean(run)
