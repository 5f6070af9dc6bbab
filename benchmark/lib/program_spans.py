"""Arithmetic over the spans the program records of itself.

The flight recorder (``pathway_tpu/engine/flight_recorder.py``) keeps one
bounded store of spans ``(name, t0, t1, cause, thread, counts)`` on
``time.perf_counter()``, the clock of :class:`benchmark.lib.record.Run`:

    tick            the commit loop awake: wake-up -> ``run_time`` returned;
                    ``rows`` drained, ``requests`` picked up
    tick.drain      around the drain, on a tick that carried rows
    tick.host       around ``run_time`` (returns with the leg submitted)
    bridge.wait     a device leg queued: submitted -> started
    bridge.leg      the leg on the bridge worker: started -> finished
    connector.pass  one polling pass of the fs source; ``files`` holds
                    (``st_mtime``, push instant) of each file it read

and a ring of operator steps ``(tick, op, leg, t0, ms, rows_in,
rows_out)``. The spans of a tick share ``cause == ("tick", n)``, and a
request-tracker record carries its ``tick``. The readers run while the
server is still up and reach the live recorder through the run's system.
Where the program keeps no such store (an untraced run; a program from
before it had one) every function returns None. A device interval of the
profile is put on this clock by subtracting ``Reduced.clock_offset_s``.
"""

from __future__ import annotations

import bisect

from benchmark.lib import stats
from benchmark.lib.trace import _union


def recorder(run):
    """The run's live flight recorder, where it keeps spans."""
    system = run.extras.get("system")
    rec = getattr(getattr(system, "runtime", None), "recorder", None)
    return rec if callable(getattr(rec, "spans", None)) else None


def named(run, name: str, t0: float | None = None,
          t1: float | None = None) -> list | None:
    """The spans ``name`` that overlap ``[t0, t1]``, oldest first."""
    rec = recorder(run)
    if rec is None:
        return None
    return [sp for sp in rec.spans(t0, t1) if sp[0] == name]


def started_in_window(run, name: str) -> list | None:
    """The spans ``name`` that started inside the window."""
    spans = named(run, name, run.w0, run.w1)
    if spans is None:
        return None
    return [sp for sp in spans if run.w0 <= sp[1] < run.w1]


def _ms(span) -> float:
    return (span[2] - span[1]) * 1e3


def _median(values) -> float | None:
    values = list(values)
    return stats.median(values) if values else None


def _overlap(intervals: list[tuple[float, float]], a: float,
             b: float) -> float:
    """Seconds of ``[a, b]`` that the disjoint ``intervals`` cover."""
    return sum(max(0.0, min(e, b) - max(s, a)) for s, e in intervals)


# -- ticks ---------------------------------------------------------------------

def tick_period_ms_p50(run) -> float | None:
    """Median start-to-start distance of consecutive ticks in the window:
    the loop's sleep plus the tick's own work."""
    ticks = started_in_window(run, "tick")
    if not ticks or len(ticks) < 2:
        return None
    starts = sorted(sp[1] for sp in ticks)
    return _median((b - a) * 1e3 for a, b in zip(starts, starts[1:]))


def tick_busy_ms_p50(run) -> float | None:
    """Median duration of the window's ticks that picked up a request."""
    ticks = started_in_window(run, "tick")
    if not ticks:
        return None
    return _median(_ms(sp) for sp in ticks
                   if (sp[5] or {}).get("requests", 0) > 0)


def tick_busy_share(run) -> float | None:
    """Percent of the window spent inside ``tick`` spans."""
    ticks = named(run, "tick", run.w0, run.w1)
    if not ticks:
        return None
    inside = _overlap([(sp[1], sp[2]) for sp in ticks], run.w0, run.w1)
    return 100.0 * inside / run.window_s


def tick_ms_per_row_drift(run) -> float | None:
    """(``tick`` + ``bridge.leg`` ms per drained row in the window's last
    third) over (the same in its first third): about 1 where a tick's
    cost per row holds through the window."""
    ticks = started_in_window(run, "tick")
    legs = named(run, "bridge.leg", run.w0)
    if not ticks:
        return None
    leg_ms = {sp[3]: _ms(sp) for sp in legs}
    third = run.window_s / 3.0

    def ms_per_row(lo: float, hi: float) -> float | None:
        part = [sp for sp in ticks if lo <= sp[1] < hi]
        rows = sum((sp[5] or {}).get("rows", 0) for sp in part)
        if not rows:
            return None
        return sum(_ms(sp) + leg_ms.get(sp[3], 0.0) for sp in part) / rows

    first = ms_per_row(run.w0, run.w0 + third)
    last = ms_per_row(run.w1 - third, run.w1)
    if not first or last is None:
        return None
    return last / first


# -- the device leg of a request -----------------------------------------------

def request_legs(run) -> list[tuple] | None:
    """(``bridge.wait``, ``bridge.leg``) of the tick of each of the
    window's requests that the store still holds both of."""
    waits, legs = named(run, "bridge.wait"), named(run, "bridge.leg")
    if waits is None or not run.requests:
        return None
    waits = {sp[3]: sp for sp in waits}
    legs = {sp[3]: sp for sp in legs}
    out = []
    for r in run.requests:
        cause = ("tick", r["tick"])
        if cause in waits and cause in legs:
            out.append((waits[cause], legs[cause]))
    return out


def bridge_wait_ms_p50(run) -> float | None:
    pairs = request_legs(run)
    return _median(_ms(wait) for wait, _leg in pairs) if pairs else None


def device_busy(run) -> list[list[tuple[float, float]]] | None:
    """Per chip, the intervals of the traced window in which an operation
    ran on it (the complement of its idle gaps), on ``perf_counter``."""
    trace = run.trace
    if trace is None or trace.clock_offset_s is None:
        return None
    off = trace.clock_offset_s
    out = []
    for dev in trace.devices:
        busy, cur = [], trace.t0
        for s, e in sorted(dev.gaps):
            if s > cur:
                busy.append((cur - off, s - off))
            cur = max(cur, e)
        if cur < trace.t1:
            busy.append((cur - off, trace.t1 - off))
        out.append(busy)
    return out


def request_device_ms(run) -> list[tuple[float, float]] | None:
    """Per request whose tick's ``bridge.leg`` lies wholly inside the
    traced part of the window: (ms a chip was busy inside the leg, on the
    chip busiest there; ms of the leg)."""
    pairs, busy = request_legs(run), device_busy(run)
    if not pairs or busy is None or run.traced is None:
        return None
    t0, t1 = run.traced
    return [(1e3 * max(_overlap(dev, leg[1], leg[2]) for dev in busy),
             _ms(leg)) for _wait, leg in pairs
            if t0 <= leg[1] and leg[2] <= t1]


def device_busy_ms_p50(run) -> float | None:
    got = request_device_ms(run)
    return _median(busy for busy, _leg in got) if got else None


def leg_host_ms_p50(run) -> float | None:
    """Median of (leg - the chip's busy time inside it): host time inside
    the device stage."""
    got = request_device_ms(run)
    return _median(leg - busy for busy, leg in got) if got else None


def idle_in_tick_wait_share(run) -> float | None:
    """Percent of the idlest chip's idle time in the traced part that lies
    outside every ``tick`` and ``bridge.leg`` span: the chip idle because
    the loop was asleep, not because the host was at work."""
    trace = run.trace
    if trace is None or trace.clock_offset_s is None or run.traced is None:
        return None
    t0, t1 = run.traced
    ticks, legs = named(run, "tick", t0, t1), named(run, "bridge.leg", t0, t1)
    if not ticks:
        return None
    at_work = _union([(sp[1], sp[2]) for sp in ticks + legs])
    off = trace.clock_offset_s
    dev = min(trace.devices, key=lambda d: d.busy_s)
    idle = outside = 0.0
    for s, e in dev.gaps:
        s, e = max(s - off, t0), min(e - off, t1)
        if e > s:
            idle += e - s
            outside += (e - s) - _overlap(at_work, s, e)
    return 100.0 * outside / idle if idle else None


# -- the connector's passes and a document's commit ----------------------------

def pass_ms_p50(run) -> float | None:
    """Median duration of the polling passes that lie inside the window."""
    passes = named(run, "connector.pass", run.w0, run.w1)
    if not passes:
        return None
    return _median(_ms(sp) for sp in passes
                   if run.w0 <= sp[1] and sp[2] <= run.w1)


def live_files(run) -> list[tuple[float, float]] | None:
    """(``st_mtime`` in wall seconds, push instant) of every file a pass
    pushed inside the window."""
    passes = named(run, "connector.pass", run.w0)
    if passes is None:
        return None
    return [(mtime, push) for sp in passes
            for mtime, push in (sp[5] or {}).get("files", ())
            if run.w0 <= push < run.w1]


def commit_ms_p50(run) -> float | None:
    """Median of (commit stamp - push) over the window's live files. The
    commit stamp of a file is the end of the ``bridge.leg`` of the first
    tick whose ``tick.drain`` started at or after the file's push: the
    leg's resolution is the moment a query as of now can see the row (with
    the bridge off, the end of that tick's ``tick.host``)."""
    files = live_files(run)
    if not files:
        return None
    drains = sorted(named(run, "tick.drain", run.w0), key=lambda sp: sp[1])
    starts = [sp[1] for sp in drains]
    ends = {sp[3]: sp[2] for sp in named(run, "tick.host", run.w0)}
    ends.update((sp[3], sp[2]) for sp in named(run, "bridge.leg", run.w0))
    waits = []
    for _mtime, push in files:
        i = bisect.bisect_left(starts, push)
        if i < len(drains) and drains[i][3] in ends:
            waits.append((ends[drains[i][3]] - push) * 1e3)
    return _median(waits)


def connector_lag_ms_p50(run) -> float | None:
    """Median of (push, as wall time - ``st_mtime``) over the window's
    live files: the file waiting for the connector's pass to reach it."""
    files = live_files(run)
    wall_ns = getattr(recorder(run), "_wall_ns_offset", None)
    if not files or wall_ns is None:
        return None
    return _median((push + wall_ns / 1e9 - mtime) * 1e3
                   for mtime, push in files)


# -- operator steps ------------------------------------------------------------

def operator_shares(run) -> list[tuple[str, float]] | None:
    """(operator, percent of the window spent in its steps), costliest
    first, over the part inside the window of every recorded operator
    step, host and device leg alike."""
    rec = recorder(run)
    if rec is None:
        return None
    total: dict[int, float] = {}
    for _tick, op_id, _leg, t0, ms, _rin, _rout in rec.tail_events(None):
        inside = min(t0 + ms / 1e3, run.w1) - max(t0, run.w0)
        if inside > 0:
            total[op_id] = total.get(op_id, 0.0) + inside
    names = {st["id"]: st["name"] for st in rec.op_stats()}
    return sorted(((names.get(op_id, f"op{op_id}"), 100.0 * s / run.window_s)
                   for op_id, s in total.items()), key=lambda kv: -kv[1])
