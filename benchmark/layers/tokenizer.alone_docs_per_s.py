"""The native tokenizer alone on the cell's documents, outside the window,
on one thread: an upper limit of what the host can ingest."""


def read(run):
    alone = run.extras.get("tokenizer_alone")
    if not alone:
        return None
    return alone["docs"] / alone["seconds"]
