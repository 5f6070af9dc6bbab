"""Median over the window's live documents of (the tick in which the index
took the document - the instant its file was in place), from the benchmark's
own polling of the index's row count every 10 ms (the program has no commit
stamp yet): the connector's poll, its listing pass, the tick and the
ingest."""

from benchmark.lib.readers import visible_ms


def read(run):
    return visible_ms(run, 50.0)
