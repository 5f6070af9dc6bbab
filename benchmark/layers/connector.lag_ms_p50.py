"""Median over the same files as ``documents.commit_ms_p50`` of (push, as
wall time - the file's ``st_mtime``): a written file waiting for the
connector's polling pass to reach it."""

from benchmark.lib.program_spans import connector_lag_ms_p50


def read(run):
    return connector_lag_ms_p50(run)
