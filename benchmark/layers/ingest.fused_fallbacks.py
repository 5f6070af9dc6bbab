"""Ingest batches in the window that the donated one-dispatch step could
not place and the two-dispatch path took (``fused_fallbacks`` after -
before): 0 where the reservation holds the run."""


def read(run):
    a, b = run.before, run.after
    if a.get("fused_fallbacks") is None:
        return None
    return float(b["fused_fallbacks"] - a["fused_fallbacks"])
