"""``connector.pass`` spans started inside the window that listed the whole
watched path: every pass of a polling reader; of a reader that waits on the
kernel's events, its first pass and one after each overflow of the event
queue. A span without ``trigger`` (a program from before the watched mode)
is a listing."""

from benchmark.lib.program_spans import started_in_window


def read(run):
    passes = started_in_window(run, "connector.pass")
    if passes is None:
        return None
    return float(sum((sp[5] or {}).get("trigger", "listing") == "listing"
                     for sp in passes))
