"""The longest a live document of the window took from its write to the
tick in which the index took it: what ``visible_within_ms`` has to cover
(the tail of ``doc_visible_p50_ms``)."""

from benchmark.lib.readers import visible_ms


def read(run):
    return visible_ms(run, 100.0)
