"""Rows the index took per ingest batch in the window (one ``add_batch`` per
tick): rows over ``fused_batches``, both after - before."""


def read(run):
    a, b = run.before, run.after
    if a.get("fused_batches") is None or b["fused_batches"] == a[
            "fused_batches"]:
        return None
    return (b["rows"] - a["rows"]) / (b["fused_batches"] - a["fused_batches"])
