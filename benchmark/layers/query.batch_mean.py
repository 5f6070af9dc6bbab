"""Mean number of queries one ``index.search`` call answered in the window:
how many queries share a tick."""


def read(run):
    spans = run.spans_in("index.search")
    if not spans:
        return None
    return sum(m["queries"] for _s, _e, m in spans) / len(spans)
