"""Percent of the key blocks up to the diagonal that the blocked attention
ran, over the window's dispatches and the model's attention layers: the
program's ``embedder.dispatch`` spans' ``attn_tiles_run`` over their
``attn_tiles_all``. What is missing from 100 is what the window, the
documents' edges inside a packed row and the row's padding spare. None
where the program's spans carry no such field."""

from benchmark.lib import program_spans


def read(run):
    spans = program_spans.started_in_window(run, "embedder.dispatch")
    counts = [sp[5] for sp in spans or () if "attn_tiles_all" in (sp[5] or {})]
    of = sum(c["attn_tiles_all"] for c in counts)
    if not of:
        return None
    return 100.0 * sum(c["attn_tiles_run"] for c in counts) / of
