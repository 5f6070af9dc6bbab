"""Flight recorder: ring-buffered per-operator tick tracing.

The reference engine exports per-operator latency gauges and OTLP spans
(src/engine/telemetry.rs:196-366); this module is the port's in-process
counterpart, sized for post-mortems rather than dashboards: a bounded ring
of operator-step events — tick, operator id + class + user frame
(internals/trace.py), host vs. device leg, rows in/out — written by the
Scheduler (engine/graph.py), and beside it one bounded store of spans on
the same ``perf_counter`` clock: ``tick`` / ``tick.drain`` / ``tick.host``
from the commit loop (engine/streaming.py), ``bridge.wait`` /
``bridge.leg`` from the bridge worker (engine/device_bridge.py),
``connector.pass`` from a polling source (io/fs) and, of a backlog's pass,
one ``connector.progress`` every 256 files read. Spans of one piece of
work share a ``cause`` — ``("tick", n)`` or ``("pass", source uid, n)`` —
and a request carries its tick, so request -> tick -> leg -> operator
steps is one chain by identifier.

Inside a leg the index and the embedder write the stages of their own work
through :func:`live_span`, with the leg's tick as their cause (None with
the bridge off, where they lie inside ``tick.host`` by time): a search is
``index.search`` holding ``search.embed`` (the query's text tokenized,
packed and uploaded; it ends at the encoder's dispatch, the embedding
stays on the device) and ``search.scan`` (first search program
dispatched -> last result fetched), its other stages as counts
(``flush_rows``, ``prepare_ms``, ``rank_ms``, ``rounds``) beside
``uploads`` and ``fetches``, the transfers between host and device that
the search made for its queries (:func:`note_transfers`); an ingest call
is ``index.add_batch`` holding ``embedder.pack`` (which holds
``embedder.tokenize``) and one ``embedder.dispatch`` a fused dispatch
(ops/knn.py, xpacks/llm/embedders.py).

Consumers:

- ``PATHWAY_TRACE_PATH`` / ``pw.run(trace_path=)`` — Chrome trace-event
  JSON (opens directly in Perfetto), host and device legs on separate
  tracks, operator spans carrying user-frame attribution;
- ``/metrics`` — per-operator latency histograms + row counters;
  ``/trace`` — the last-N-ticks buffer as JSON (engine/http_server.py);
- post-mortem dumps — watchdog fire and device-bridge poison each embed
  :meth:`FlightRecorder.dump_tail`, so a hung run names its stuck
  operator instead of nothing;
- a configured OTel SDK — recorded spans flow through the run's
  ``Telemetry`` provider (internals/telemetry.py) with real timestamps.

Cost model: **disabled is the default and costs one predictable branch per
operator step, no allocation** (the Scheduler holds ``recorder=None`` or an
``enabled=False`` recorder; both short-circuit before any tuple is built).
Enabled, idle steps (zero rows either way, sub-millisecond) are not
recorded at all: the ring buffer holds the last N *active* ticks, so a
quiescent streaming server cannot flush out the spans of the ticks that
actually served requests.
Enabled, each step appends one tuple to a deque and bumps a fixed-bucket
histogram under a lock — the lock is uncontended except when a device leg
retires concurrently with host work.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time
import weakref

# Prometheus-style latency buckets (ms). +Inf is implicit as the last
# cumulative bucket. Chosen to straddle both sub-ms host operators and
# multi-second device legs (a cold compile lands in one).
LATENCY_BUCKETS_MS = (
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
    100.0, 250.0, 500.0, 1000.0, 2500.0, 10_000.0,
)

# the operator-event ring holds a 30 s window of a busy ingest (13 ticks a
# second, some twenty steps a tick) and the minutes of checks after it
_DEFAULT_BUFFER_EVENTS = 65_536
# every tick of several minutes: one ``tick`` and two ``bridge.*`` spans a
# tick, two more on a tick that carried rows, six a search and four a
# second of a backlog's pass (the operator ring's size; memory only while
# a recorder is on)
_SPAN_BUFFER = 65_536
_DEFAULT_TAIL_TICKS = 8


def atomic_write_json(path: str, payload) -> str:
    """Serialize ``payload`` to ``path`` atomically: write to a unique
    sibling tmp file, fsync, then rename, then fsync the CONTAINING
    directory. A crash mid-write can never leave a truncated, unloadable
    file at ``path`` (and never clobbers a previous good one); the tmp is
    removed on failure. The directory fsync is load-bearing for the
    evidence files (BENCH_LASTGOOD.json / BENCH_HISTORY.jsonl): on ext4
    the rename itself lives in the directory's metadata, so a crash
    right after ``os.replace`` could otherwise roll the directory back
    to the OLD entry and lose the checkpoint the data fsync already made
    durable."""
    from pathway_tpu.testing import faults

    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(payload, f)
            f.flush()
            os.fsync(f.fileno())
        # crash edge between the data fsync and the rename — the
        # durable tmp must never shadow the previous good ``path``
        faults.hit("fs.atomic_write.replace", path=str(path))
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    fsync_dir(os.path.dirname(os.path.abspath(path)))
    return path


def fsync_dir(dirpath: str) -> None:
    """fsync a directory so a just-renamed entry survives a crash
    (see :func:`atomic_write_json`). Platforms whose directories cannot
    be opened or fsynced degrade silently — the rename still happened;
    only its crash durability is best-effort there. Fault point
    ``fs.atomic_write.dirsync`` simulates the crash landing between the
    rename and this sync."""
    from pathway_tpu.testing import faults

    faults.hit("fs.atomic_write.dirsync", dir=dirpath)
    try:
        fd = os.open(dirpath, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)

# live enabled recorders (weak: a recorder dies with its scheduler/run).
# Lets out-of-band observers — the profiler's host sampler — find the
# run's in-flight operator without plumbing a reference through every layer.
_LIVE: "weakref.WeakSet[FlightRecorder]" = weakref.WeakSet()


def live_inflight() -> dict | None:
    """The in-flight operator summary of any live enabled recorder
    (None when nothing is recording or nothing is in flight)."""
    for rec in list(_LIVE):
        if rec.enabled:
            info = rec.inflight_summary()
            if info is not None:
                return info
    return None


def live_inflight_by_thread() -> dict:
    """{thread ident: (leg, operator name)} for every live enabled
    recorder's in-flight operators — the profiler's host sampler reads
    this to tag samples with the operator the sampled thread was
    stepping (engine/profiler.py). Empty dict when nothing records."""
    out: dict = {}
    for rec in list(_LIVE):
        if rec.enabled:
            out.update(rec.inflight_by_thread())
    return out


def live_span(name: str, t0: float, t1: float, **counts) -> None:
    """One finished span into every live enabled recorder, from code that
    holds no reference to the run (an index inside a device leg). Its
    cause is the leg in flight, so it joins that tick's other spans."""
    for rec in list(_LIVE):
        if rec.enabled:
            leg = rec._inflight_leg
            rec.span(name, t0, t1, ("tick", leg[0]) if leg else None,
                     **counts)


def recording() -> bool:
    """Whether any live recorder is on: callers that must compute a span's
    counts ask first."""
    return any(rec.enabled for rec in list(_LIVE))


# the counts dict of the ``index.search`` this thread is inside while a
# recorder is on: the embedder's upload and the index's fetches are made
# in code that holds no reference to it
_TRANSFERS = threading.local()


@contextlib.contextmanager
def counting_transfers(counts: dict):
    """``counts`` gains ``uploads`` and ``fetches``: the host-to-device and
    device-to-host transfers :func:`note_transfers` is told of on this
    thread inside the block. Opened by whoever writes the span the counts
    go on, so only while a recorder is on."""
    counts.update(uploads=0, fetches=0)
    _TRANSFERS.counts = counts
    try:
        yield counts
    finally:
        _TRANSFERS.counts = None


def note_transfers(uploads: int = 0, fetches: int = 0) -> None:
    """Transfers the caller has just made between host and device, for
    the search that counts them on this thread (no-op outside one)."""
    counts = getattr(_TRANSFERS, "counts", None)
    if counts is not None:
        counts["uploads"] += uploads
        counts["fetches"] += fetches


def attach_note(e: BaseException, note: str) -> None:
    """PEP 678 note with the pre-3.11 emulation (same storage contract as
    internals/trace.py add_trace_note, shared here so exceptions raised on
    the bridge worker can carry the recorder tail across threads)."""
    if note in getattr(e, "__notes__", ()):
        return
    if hasattr(e, "add_note"):
        e.add_note(note)
    else:
        notes = getattr(e, "__notes__", None)
        if notes is None:
            notes = []
            e.__notes__ = notes
        notes.append(note)


class _OpStats:
    """Per-operator aggregate: fixed-bucket latency histogram + row
    counters + identity (name, operator class, user frame) captured once.
    ``rederived``: times a reducer state of the operator derived its order
    from a whole group (engine/reducers.py); one that grows with the ticks
    means a step costs time linear in the group again."""

    __slots__ = ("name", "op_class", "frame", "bucket_counts", "sum_ms",
                 "count", "rows_in", "rows_out", "rederived")

    def __init__(self, name: str, op_class: str, frame: str | None):
        self.name = name
        self.op_class = op_class
        self.frame = frame
        self.bucket_counts = [0] * (len(LATENCY_BUCKETS_MS) + 1)
        self.sum_ms = 0.0
        self.count = 0
        self.rows_in = 0
        self.rows_out = 0
        self.rederived = 0

    def observe(self, ms: float, rows_in: int, rows_out: int,
                rederived: int = 0) -> None:
        i = 0
        for b in LATENCY_BUCKETS_MS:
            if ms <= b:
                break
            i += 1
        self.bucket_counts[i] += 1
        self.sum_ms += ms
        self.count += 1
        self.rows_in += rows_in
        self.rows_out += rows_out
        self.rederived += rederived


class FlightRecorder:
    """Ring-buffered span recorder for one scheduler (see module doc)."""

    def __init__(self, trace_path: str | None = None,
                 buffer_events: int | None = None):
        self.enabled = False
        self.trace_path = trace_path
        if buffer_events is None:
            from pathway_tpu.internals.config import _env_int

            buffer_events = max(256, _env_int("PATHWAY_TRACE_BUFFER_EVENTS",
                                              _DEFAULT_BUFFER_EVENTS))
        from pathway_tpu.engine.locking import create_lock

        self._lock = create_lock("FlightRecorder._lock")
        # (tick, op_id, leg, t0_perf, dur_ms, rows_in, rows_out)
        self._events: collections.deque = collections.deque(
            maxlen=buffer_events)
        self._ops: dict[int, _OpStats] = {}
        # (name, t0_perf, t1_perf, cause, thread ident, counts or None)
        self._spans: collections.deque = collections.deque(
            maxlen=_SPAN_BUFFER)
        # thread ident -> name of every thread that wrote a span: reader
        # threads are gone by the time the trace file is written
        self._span_threads: dict[int, str] = {}
        # in-flight markers, ONE SLOT PER STEPPING THREAD: host thread(s),
        # sharded pool workers and the bridge worker each own the slot
        # keyed by their thread id, so a device op hung for minutes keeps
        # its marker while other threads churn theirs (the whole point of
        # stall attribution). Dict item set/del is atomic under the GIL.
        self._inflight_op: dict = {}
        # thread id -> (tick, leg, node, started_monotonic)
        self._inflight_leg = None  # (tick, dispatched_monotonic)
        # trace time base: perf_counter for durations, wall ns for OTel
        self._epoch = time.perf_counter()
        self._wall_ns_offset = time.time_ns() - int(self._epoch * 1e9)
        self._otel = None
        self._jax_annotation = None  # cached class / False after probe
        # request-scoped serving spans (engine/request_tracker.py): set on
        # enabled recorders by from_env; None keeps every per-request hook
        # a dead branch
        self.requests = None
        # fleet identity (engine/fleet_observability.py): stamped by the
        # streaming runtime so the written trace names its process and the
        # trace merger can place it on the right fleet track
        self.role = "primary"
        self.process = (os.environ.get("PATHWAY_REPLICA_ID")
                        or f"pid{os.getpid()}")
        # (perf_counter, epoch, complete_tick) of a replica→primary
        # promotion; drawn as an instant on this track and, in the
        # merged fleet trace, as the timeline-handoff flow arrow from
        # the dead primary (engine/fleet_observability.merge_traces)
        self._promotion: tuple[float, int, int] | None = None

    # -- construction ------------------------------------------------------
    @classmethod
    def from_env(cls, trace_path: str | None = None,
                 auto_on: bool = False) -> "FlightRecorder | None":
        """The run-level recorder, or None when recording is off.

        Enabled when a trace path is given (argument or
        ``PATHWAY_TRACE_PATH``), when ``PATHWAY_FLIGHT_RECORDER`` is
        truthy, or when the caller's surface makes the data observable
        (``auto_on``: http server / live dashboard).
        ``PATHWAY_FLIGHT_RECORDER=0`` force-disables everything."""
        flag = os.environ.get("PATHWAY_FLIGHT_RECORDER", "").strip().lower()
        if flag in ("0", "false", "off", "no"):
            return None
        tp = trace_path or os.environ.get("PATHWAY_TRACE_PATH") or None
        forced = flag in ("1", "true", "on", "yes")
        if tp is None and not forced and not auto_on:
            return None
        rec = cls(trace_path=tp)
        rec.enabled = True
        from pathway_tpu.engine.request_tracker import RequestTracker

        rec.requests = RequestTracker()
        _LIVE.add(rec)
        return rec

    def set_telemetry(self, telemetry) -> None:
        """Route recorded spans through the run's OTel provider — only
        when a real SDK pipeline is wired (API-only mode would pay span
        construction for a no-op exporter)."""
        if telemetry is not None \
                and getattr(telemetry, "_provider", None) is not None:
            self._otel = telemetry

    # -- hot-path write side ----------------------------------------------
    def mark_op(self, tick: int, node, leg: str) -> None:
        self._inflight_op[threading.get_ident()] = (
            tick, leg, node, time.monotonic())

    def clear_op(self) -> None:
        self._inflight_op.pop(threading.get_ident(), None)

    def inflight_by_thread(self) -> dict:
        """{thread ident: (leg, operator name)} of operators currently
        being stepped, keyed by the stepping thread. Read lock-free by
        the profiler's sampler: _inflight_op is only ever mutated by
        single-item dict ops, so a racy read sees either the old or the
        new entry, both of which were true moments ago."""
        out = {}
        for ident, slot in list(self._inflight_op.items()):
            try:
                tick, leg, node, _t0 = slot
            except (TypeError, ValueError):
                continue
            out[ident] = (leg, node.name or type(node.op).__name__)
        return out

    def record(self, tick: int, node, leg: str, t0: float, dur_ms: float,
               rows_in: int, rows_out: int, rederived: int = 0) -> None:
        with self._lock:
            st = self._ops.get(node.id)
            if st is None:
                trace = getattr(node, "trace", None)
                st = self._ops[node.id] = _OpStats(
                    node.name or type(node.op).__name__,
                    type(node.op).__name__,
                    str(trace) if trace is not None else None)
            st.observe(dur_ms, rows_in, rows_out, rederived)
            self._events.append(
                (tick, node.id, leg, t0, dur_ms, rows_in, rows_out))
        if self._otel is not None:
            self._emit_otel_span(st, tick, leg, t0, dur_ms, rows_in,
                                 rows_out)

    def mark_leg(self, tick: int) -> None:
        self._inflight_leg = (tick, time.monotonic())

    def clear_leg(self) -> None:
        self._inflight_leg = None

    def span(self, name: str, t0: float, t1: float, cause=None,
             **counts) -> None:
        """One finished span ``[t0, t1]`` (``perf_counter`` seconds) of the
        calling thread. ``cause`` is the identifier the spans of one piece
        of work share; ``counts`` is what the work amounted to."""
        ident = threading.get_ident()
        with self._lock:
            if ident not in self._span_threads:
                self._span_threads[ident] = threading.current_thread().name
            self._spans.append((name, t0, t1, cause, ident, counts or None))

    def spans(self, t0: float | None = None,
              t1: float | None = None) -> list[tuple]:
        """The buffered spans that overlap ``[t0, t1]`` (an open end is
        unbounded), oldest first."""
        with self._lock:
            out = list(self._spans)
        if t0 is not None:
            out = [sp for sp in out if sp[2] >= t0]
        if t1 is not None:
            out = [sp for sp in out if sp[1] <= t1]
        return out

    def note_promotion(self, epoch: int, complete_tick: int) -> None:
        """Stamp the moment this process was promoted to primary
        (engine/streaming.py failover): the written trace carries it as
        a process-scoped instant, and the fleet merger draws the
        timeline handoff from the dead primary's track to it."""
        self._promotion = (time.perf_counter(), int(epoch),
                           int(complete_tick))

    def device_annotation(self, tick: int):
        """``jax.profiler.TraceAnnotation`` for one device leg, so XLA
        profiles line up with framework spans; nullcontext when jax is
        unavailable. The class lookup is probed once."""
        if self._jax_annotation is None:
            try:
                from jax.profiler import TraceAnnotation

                self._jax_annotation = TraceAnnotation
            except Exception:
                self._jax_annotation = False
        if self._jax_annotation is False:
            return contextlib.nullcontext()
        return self._jax_annotation(f"pathway.device_leg.t{tick}")

    def _emit_otel_span(self, st: _OpStats, tick: int, leg: str, t0: float,
                        dur_ms: float, rows_in: int, rows_out: int) -> None:
        try:
            start_ns = int(t0 * 1e9) + self._wall_ns_offset
            span = self._otel.tracer.start_span(
                f"pathway.operator.{st.name}", start_time=start_ns)
            span.set_attribute("pathway.tick", tick)
            span.set_attribute("pathway.leg", leg)
            span.set_attribute("pathway.operator_class", st.op_class)
            span.set_attribute("pathway.rows_in", rows_in)
            span.set_attribute("pathway.rows_out", rows_out)
            if st.frame:
                span.set_attribute("pathway.user_frame", st.frame)
            span.end(end_time=start_ns + int(dur_ms * 1e6))
        except Exception:  # noqa: BLE001 — telemetry must never kill a step
            self._otel = None

    # -- read side ---------------------------------------------------------
    def op_stats(self) -> list[dict]:
        """Histogram snapshot per operator (for /metrics): cumulative
        bucket counts, sum/count, row totals."""
        with self._lock:
            items = [(op_id, st.name, st.op_class, st.frame,
                      list(st.bucket_counts), st.sum_ms, st.count,
                      st.rows_in, st.rows_out, st.rederived)
                     for op_id, st in self._ops.items()]
        out = []
        for (op_id, name, op_class, frame, counts, sum_ms, count,
             rows_in, rows_out, rederived) in items:
            cum = []
            acc = 0
            for le, c in zip(LATENCY_BUCKETS_MS, counts):
                acc += c
                cum.append((le, acc))
            cum.append((float("inf"), acc + counts[-1]))
            out.append({
                "id": op_id, "name": name, "op_class": op_class,
                "frame": frame, "buckets": cum, "sum_ms": sum_ms,
                "count": count, "rows_in": rows_in, "rows_out": rows_out,
                "rederived": rederived,
            })
        return out

    def tail_events(self, n_ticks: int | None = None) -> list[tuple]:
        """The buffered events of the last ``n_ticks`` distinct ticks
        (all buffered events when None), oldest first."""
        with self._lock:
            evs = list(self._events)
        if n_ticks is None or not evs:
            return evs
        keep: set = set()
        for ev in reversed(evs):  # ticks appear in decreasing order
            if ev[0] not in keep:
                if len(keep) >= n_ticks:
                    break
                keep.add(ev[0])
        return [ev for ev in evs if ev[0] in keep]

    def _op_meta(self, op_id: int) -> tuple[str, str | None]:
        with self._lock:
            st = self._ops.get(op_id)
        if st is None:
            return (f"op{op_id}", None)
        return (st.name, st.frame)

    def inflight_summary(self) -> dict | None:
        """The operator currently stepping (plus its leg/frame) — the
        post-mortem answer to "what was the engine doing when it hung"."""
        slots = list(self._inflight_op.values())
        now = time.monotonic()
        if slots:
            # several threads mid-step: name the one stuck longest
            tick, leg, node, started = min(slots, key=lambda s: s[3])
            trace = getattr(node, "trace", None)
            return {
                "tick": tick,
                "leg": leg,
                "operator": node.name or type(node.op).__name__,
                "op_class": type(node.op).__name__,
                "user_frame": str(trace) if trace is not None else None,
                "since_s": round(now - started, 3),
            }
        leg = self._inflight_leg
        if leg is not None:
            return {"tick": leg[0], "leg": "device", "operator": None,
                    "op_class": None, "user_frame": None,
                    "since_s": round(now - leg[1], 3)}
        return None

    def dump_tail(self, n_ticks: int = _DEFAULT_TAIL_TICKS,
                  max_lines: int = 60) -> str:
        """Human-readable post-mortem block: the last-N-ticks span tail
        plus the currently in-flight leg with its operator and user frame.
        Empty string when nothing was recorded."""
        evs = self.tail_events(n_ticks)
        lines = []
        for tick, op_id, leg, _t0, dur_ms, rows_in, rows_out in \
                evs[-max_lines:]:
            name, _ = self._op_meta(op_id)
            lines.append(f"  tick {tick} [{leg}] {name}: {dur_ms:.2f}ms "
                         f"rows {rows_in}->{rows_out}")
        info = self.inflight_summary()
        if info is not None:
            who = info["operator"] or "device leg"
            lines.append(
                f"  IN FLIGHT: tick {info['tick']} [{info['leg']}] {who} "
                f"({info['since_s']:.1f}s since dispatch)")
            if info.get("user_frame"):
                for fl in info["user_frame"].splitlines():
                    lines.append(f"  {fl}")
        return "\n".join(lines)

    def trace_payload(self, n_ticks: int | None = None) -> dict:
        """JSON-friendly snapshot for the ``/trace`` endpoint."""
        evs = self.tail_events(n_ticks)
        events = []
        for tick, op_id, leg, t0, dur_ms, rows_in, rows_out in evs:
            name, frame = self._op_meta(op_id)
            events.append({
                "tick": tick, "operator": name, "id": op_id, "leg": leg,
                "ts_ms": round((t0 - self._epoch) * 1e3, 3),
                "dur_ms": round(dur_ms, 3),
                "rows_in": rows_in, "rows_out": rows_out,
                "user_frame": frame,
            })
        # with a tick limit, the spans from the first kept event on
        raw = self.spans(t0=evs[0][3] if n_ticks is not None and evs
                         else None)
        with self._lock:
            threads = dict(self._span_threads)
        spans = [{"name": name,
                  "ts_ms": round((t0 - self._epoch) * 1e3, 3),
                  "dur_ms": round((t1 - t0) * 1e3, 3),
                  "cause": list(cause) if cause is not None else None,
                  "thread": threads.get(ident, str(ident)),
                  "counts": counts}
                 for name, t0, t1, cause, ident, counts in raw]
        # a leg is a span; its wait is the ``bridge.wait`` of the same tick
        waits = self._wait_ms_by_cause(raw)
        legs = [{"tick": cause[1],
                 "queue_wait_ms": waits.get(cause, 0.0),
                 "exec_ms": round((t1 - t0) * 1e3, 3)}
                for name, t0, t1, cause, _ident, _counts in raw
                if name == "bridge.leg"]
        out = {"enabled": self.enabled, "events": events, "spans": spans,
               "device_legs": legs, "inflight": self.inflight_summary()}
        if self.requests is not None:
            out["requests"] = {
                "summary": self.requests.summary(),
                "completed": [
                    {k: r[k] for k in ("request_id", "route", "tick",
                                       "e2e_ms", "stages",
                                       "dominant_stage", "over_budget")}
                    for r in self.requests.trace_spans()[-32:]
                ],
            }
        return out

    @staticmethod
    def _wait_ms_by_cause(spans: list[tuple]) -> dict:
        """cause -> ms its device leg waited in the bridge's queue."""
        return {sp[3]: round((sp[2] - sp[1]) * 1e3, 3) for sp in spans
                if sp[0] == "bridge.wait"}

    def dominator(self) -> dict | None:
        """The operator that dominated the last complete tick (critical
        path attribution for /status and the dashboard)."""
        evs = self.tail_events(1)
        if not evs:
            return None
        tick = evs[-1][0]
        best = None
        total = 0.0
        for ev in evs:
            total += ev[4]
            if best is None or ev[4] > best[4]:
                best = ev
        name, frame = self._op_meta(best[1])
        return {"tick": tick, "operator": name, "leg": best[2],
                "ms": round(best[4], 3),
                "share": round(best[4] / total, 3) if total > 0 else 0.0,
                "user_frame": frame}

    # -- Chrome trace-event export ----------------------------------------
    @staticmethod
    def _nested(pid: int, tid: int, slices: list[tuple]) -> list[dict]:
        """B/E events of one track from ``(start_us, end_us, name, cat,
        args)`` slices, nested like a call stack so the file opens in
        Perfetto: a slice that outlasts the one it starts in (clock
        rounding at a shared edge; sharded replicas stepping side by side)
        is cut at that one's end."""
        out: list[dict] = []
        stack: list[tuple[float, str, str]] = []   # (end_us, name, cat)

        def close_until(ts: float) -> None:
            while stack and stack[-1][0] <= ts:
                end, name, cat = stack.pop()
                out.append({"ph": "E", "pid": pid, "tid": tid, "ts": end,
                            "cat": cat, "name": name})

        for start, end, name, cat, args in sorted(
                slices, key=lambda sl: (sl[0], -sl[1])):
            close_until(start)
            if stack:
                end = min(end, stack[-1][0])
            out.append({"ph": "B", "pid": pid, "tid": tid, "ts": start,
                        "cat": cat, "name": name, "args": args})
            stack.append((end, name, cat))
        close_until(float("inf"))
        return out

    def chrome_trace_events(self) -> list[dict]:
        """Trace-event list: the host leg (tid 0: ``tick`` spans holding
        ``tick.drain``, ``tick.host`` and the host operators), the device
        leg (tid 1: ``bridge.leg`` spans holding the device operators;
        ``bridge.wait`` as async events, since a leg waits while the one
        before it runs), requests (tid 2) and one track per other thread
        that wrote spans (a connector's passes), named after the thread.
        A span of any other name lies on the track of the thread that
        wrote it: the index's and the embedder's stages under their
        ``bridge.leg`` (under ``tick.host`` with the bridge off).
        Every slice is a recorded span or operator step."""
        pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
        tids = {"host": 0, "device": 1}
        out = [
            {"ph": "M", "pid": pid, "tid": 0, "name": "process_name",
             "args": {"name": f"{self.role}:{self.process}"}},
        ]
        out.extend(
            {"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
             "args": {"name": f"{leg} leg"}}
            for leg, tid in tids.items()
        )
        if self._promotion is not None:
            t_p, epoch, complete_tick = self._promotion
            out.append({
                "ph": "i", "s": "p", "pid": pid, "tid": 0,
                "ts": (t_p - self._epoch) * 1e6, "cat": "promotion",
                "name": f"promoted to primary (epoch {epoch})",
                "args": {"epoch": epoch, "complete_tick": complete_tick}})

        def us(t: float) -> float:
            return (t - self._epoch) * 1e6

        tracks: dict[int, list[tuple]] = {0: [], 1: []}
        for tick, op_id, leg, t0, dur_ms, rows_in, rows_out in \
                self.tail_events(None):
            name, frame = self._op_meta(op_id)
            args = {"tick": tick, "operator": name,
                    "rows_in": rows_in, "rows_out": rows_out}
            if frame:
                args["user_frame"] = frame
            tracks[tids[leg]].append(
                (us(t0), us(t0) + dur_ms * 1e3, name, leg, args))
        spans = self.spans()
        with self._lock:
            threads = dict(self._span_threads)
        waits = self._wait_ms_by_cause(spans)
        # where a request's flow arrows land: the start of its tick's
        # ``tick`` span and of that tick's ``bridge.leg``
        flow_start_us: dict[tuple, float] = {}
        # the commit loop's and the bridge worker's threads have theirs
        own_tids = {sp[4]: 0 if sp[0] == "tick" else 1 for sp in spans
                    if sp[0] in ("tick", "bridge.leg")}
        other_tids: dict[int, int] = {}
        for name, t0, t1, cause, ident, counts in spans:
            args = dict(counts or ())
            label = name
            if cause is not None:
                args["cause"] = list(cause)
                label = f"{name} {cause[-1]}"
            if name == "bridge.wait":
                fid = f"wait-{cause[-1]}"
                for ph, t in (("b", t0), ("e", t1)):
                    out.append({"ph": ph, "cat": "bridge", "id": fid,
                                "pid": pid, "tid": 1, "ts": us(t),
                                "name": label, "args": args})
                continue
            if name.startswith("tick"):
                tid = 0
                if name == "tick":
                    flow_start_us[(cause[1], "host")] = us(t0)
            elif name == "bridge.leg":
                tid = 1
                flow_start_us[(cause[1], "device")] = us(t0)
                args["exec_ms"] = round((t1 - t0) * 1e3, 3)
                if cause in waits:
                    args["queue_wait_ms"] = waits[cause]
            else:
                tid = own_tids.get(ident, other_tids.get(ident))
                if tid is None:
                    # tid 2 is the requests track
                    tid = other_tids[ident] = 3 + len(other_tids)
                    tracks[tid] = []
                    out.append({"ph": "M", "pid": pid, "tid": tid,
                                "name": "thread_name", "args": {
                                    "name": threads.get(ident, str(ident))}})
            tracks[tid].append((us(t0), us(t1), label, "span", args))
        for tid, slices in tracks.items():
            out.extend(self._nested(pid, tid, slices))
        out.extend(self._request_trace_events(pid, flow_start_us))
        return out

    def _request_trace_events(self, pid: int,
                              flow_start_us: dict) -> list[dict]:
        """Third track: completed request spans as async (b/e) events —
        async because concurrent requests legitimately overlap, which
        B/E nesting cannot represent — with per-stage child spans and a
        flow arrow (s -> t -> f) from each request's tick-start into its
        tick's ``tick`` span and ``bridge.leg``, so clicking a query walks
        to the operator spans that served it."""
        tracker = self.requests
        spans = tracker.trace_spans() if tracker is not None else []
        if not spans:
            return []
        out = [{"ph": "M", "pid": pid, "tid": 2, "name": "thread_name",
                "args": {"name": "requests"}}]
        from pathway_tpu.engine.request_tracker import STAGES

        for i, r in enumerate(spans):
            stamps_us = [(t - self._epoch) * 1e6 for t in r["stamps"]]
            rid = r["request_id"]
            fid = f"req-{rid}"
            name = f"req {rid}"
            args = {"request_id": rid, "route": r["route"],
                    "tick": r["tick"], "e2e_ms": r["e2e_ms"],
                    "dominant_stage": r["dominant_stage"],
                    **{f"{k}_ms": v for k, v in r["stages"].items()}}
            out.append({"ph": "b", "cat": "request", "id": fid, "pid": pid,
                        "tid": 2, "ts": stamps_us[0], "name": name,
                        "args": args})
            for j, stage in enumerate(STAGES):
                if stamps_us[j + 1] - stamps_us[j] <= 0.0:
                    continue
                out.append({"ph": "b", "cat": "request", "id": fid,
                            "pid": pid, "tid": 2, "ts": stamps_us[j],
                            "name": stage})
                out.append({"ph": "e", "cat": "request", "id": fid,
                            "pid": pid, "tid": 2,
                            "ts": stamps_us[j + 1], "name": stage})
            out.append({"ph": "e", "cat": "request", "id": fid, "pid": pid,
                        "tid": 2, "ts": stamps_us[-1], "name": name})
            tick = r["tick"]
            if tick is None:
                continue
            host_us = flow_start_us.get((tick, "host"))
            dev_us = flow_start_us.get((tick, "device"))
            targets = [(0, host_us), (1, dev_us)]
            targets = [(tid, ts) for tid, ts in targets if ts is not None]
            if not targets:
                continue
            # flow: s inside the request span at tick pickup, then one
            # step/finish per leg the request crossed
            out.append({"ph": "s", "cat": "request", "id": fid,
                        "pid": pid, "tid": 2, "ts": stamps_us[2],
                        "name": "request"})
            for k, (tid, ts) in enumerate(targets):
                ph = "f" if k == len(targets) - 1 else "t"
                ev = {"ph": ph, "cat": "request", "id": fid, "pid": pid,
                      "tid": tid, "ts": ts + 0.01, "name": "request"}
                if ph == "f":
                    ev["bp"] = "e"
                out.append(ev)
        return out

    def chrome_trace_payload(self) -> dict:
        """The full Chrome-trace payload incl. the ``pathway_meta`` fleet
        block (os pid, role, process label, and the monotonic↔wall clock
        anchor) that lets ``fleet_observability.merge_traces`` place this
        process's events on the shared wall-clock timeline. Served live by
        ``/trace?format=chrome`` and written by
        :meth:`write_chrome_trace`."""
        # wall-clock microsecond that this trace's ts==0 (the recorder
        # epoch) maps to: events are (t - epoch) * 1e6, and
        # epoch_wall_ns = epoch * 1e9 + _wall_ns_offset by construction
        epoch_wall_us = (self._epoch * 1e9 + self._wall_ns_offset) / 1e3
        return {
            "traceEvents": self.chrome_trace_events(),
            "displayTimeUnit": "ms",
            # per-operator totals over the whole run (the ring above holds
            # the newest steps only)
            "pathway_operators": [
                {k: st[k] for k in ("id", "name", "count", "sum_ms",
                                    "rows_in", "rows_out", "rederived")}
                for st in self.op_stats()],
            "pathway_meta": {
                "pid": os.getpid(),
                "process": self.process,
                "role": self.role,
                "epoch_wall_us": epoch_wall_us,
                # the perf_counter value ts==0 maps to: lets a consumer
                # holding only a heartbeat clock anchor (wall - perf)
                # recompute epoch_wall_us independently
                "epoch_perf": self._epoch,
            },
        }

    def write_chrome_trace(self, path: str | None = None) -> str | None:
        """Serialize the buffer to Chrome trace JSON at ``path`` (defaults
        to the configured trace_path); returns the path written or None."""
        path = path or self.trace_path
        if not path:
            return None
        # atomic (unique tmp + fsync + rename + dir fsync): a crash
        # mid-write must not leave a truncated trace, nor clobber the
        # previous good one
        return atomic_write_json(path, self.chrome_trace_payload())
