"""The longest collection of the interpreter's garbage collector inside the
window: it holds the GIL, so the commit loop, the bridge and the web server
all stop for it, and the queries that arrive meanwhile share the next tick."""


def read(run):
    pauses = run.extras.get("gc_pauses")
    if not pauses:
        return None
    return 1e3 * max(seconds for _t, _gen, seconds in pauses)
