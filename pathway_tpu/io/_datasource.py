"""Streaming datasource machinery shared by all input connectors.

Rebuild of the reference's connector framework (src/connectors/mod.rs:400 —
per-connector input thread parsing entries into a channel drained by the
main loop each commit). A DataSource runs on its own thread and pushes
parsed rows into a session; the streaming runtime drains sessions, assigns
the next logical timestamp, and steps the scheduler.
"""

from __future__ import annotations

import itertools
import queue
import threading
import weakref
from typing import Any, Callable

from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import schema as sch
from pathway_tpu.internals.keys import Pointer, hash_values

_source_counter = itertools.count()


class Session:
    """Thread-safe buffer between a connector thread and the scheduler."""

    def __init__(self):
        self._q: queue.Queue = queue.Queue()
        self.closed = threading.Event()
        # terminal state: a session closed by a crashing reader is NOT
        # end-of-stream (reference: the main loop observes connector thread
        # death, src/connectors/mod.rs) — the supervisor inspects the reason
        # to decide between finishing, restarting, and escalating
        self.closed_reason: str | None = None  # "eos" | "error"
        self.error: BaseException | None = None
        # set by the runtime at teardown; polling sources observe it via
        # stop_requested / sleep() so reader threads actually terminate
        # (reference: connector threads exit when the main loop drops the
        # channel, src/connectors/mod.rs)
        self.stopping = threading.Event()
        # QoS backpressure (engine/qos.py): while the controller is
        # deferring ingest to protect query latency, the supervisor
        # raises this flag and sleep() stretches the reader's poll
        # interval — producers slow down instead of growing the backlog
        self.backpressure = threading.Event()
        self.backpressure_factor = 4.0
        # the run's flight recorder (engine/flight_recorder.py), filled by
        # the streaming runtime while one records: a polling source writes
        # one ``connector.pass`` span per pass through it. None = off
        self.recorder = None
        # filled by the streaming runtime for the session of a serving
        # source (rest_connector) alone: called after every pushed
        # insertion, it ends the commit loop's wait, so a request is
        # drained by a tick that starts at once and the autocommit period
        # is the longest it can wait. None (every ingest source): a push
        # wakes nobody and rides the period's tick
        self.wake: Callable[[], None] | None = None

    @property
    def stop_requested(self) -> bool:
        return self.stopping.is_set()

    def sleep(self, seconds: float) -> bool:
        """Pause between polls, waking immediately on a stop request.
        Returns True to keep running, False when the source must exit.
        While QoS backpressure is up the pause stretches, throttling the
        producer at its own cadence."""
        if self.backpressure.is_set():
            seconds = seconds * self.backpressure_factor
        return not self.stopping.wait(seconds)

    def push(self, key: Pointer, row: tuple, diff: int = 1,
             offset: Any = None) -> None:
        # `offset` is the source's durable position for this entry; it is
        # consumed by the persistence layer's RecordingSession proxy
        # (engine/persistence.py) and ignored on the plain live path.
        self._q.put((key, row, diff))
        if self.wake is not None and diff > 0:
            # a request; its retraction, once answered (diff < 0), needs
            # no tick of its own and rides the next one
            self.wake()

    def drain(self, limit: int | None = None) -> list[tuple]:
        """Pop buffered entries (all of them, or at most ``limit`` when
        the QoS controller budgets this tick's ingest — the remainder
        stays queued and rides later ticks)."""
        out = []
        while limit is None or len(out) < limit:
            try:
                out.append(self._q.get_nowait())
            except queue.Empty:
                return out
        return out

    def backlog(self) -> int:
        """Approximate queued-entry count (producer threads may race it;
        used only for deferral accounting and observability)."""
        return self._q.qsize()

    def close(self, reason: str = "eos",
              error: BaseException | None = None) -> None:
        if not self.closed.is_set():  # first close wins
            self.closed_reason = reason
            self.error = error
        self.closed.set()


class DataSource:
    """Base class: subclasses implement run(session) on a worker thread."""

    name = "datasource"
    # restart/escalation policy (engine/supervisor.py ConnectorPolicy);
    # None means the runtime's default policy applies
    connector_policy = None
    # restart semantics for the supervisor's in-process restarts: False
    # (default) = a restarted run() re-emits the stream from the start, so
    # the supervisor skips the already-delivered prefix; True = run()
    # resumes from externally-tracked offsets (e.g. a Kafka consumer
    # group), so nothing already delivered is re-emitted and nothing may
    # be skipped
    restart_resumes = False

    def __init__(self, schema: type[sch.Schema],
                 autocommit_duration_ms: int | None = 1500):
        self.schema = schema
        self.autocommit_duration_ms = autocommit_duration_ms
        self._uid = next(_source_counter)

    def start(self, session: Session) -> threading.Thread:
        def runner():
            # capture the exception instead of swallowing it: a crashed
            # reader closing its session as if end-of-stream would let the
            # runtime flush, checkpoint, and report success on partial data
            try:
                self.run(session)
            except BaseException as e:
                session.close(reason="error", error=e)
            else:
                session.close(reason="eos")

        from pathway_tpu.engine.threads import spawn

        # factory-spawned (engine/threads.py): inventory + excepthook
        # coverage; the wrapper above still owns reader-crash semantics
        # (the supervisor restarts, the excepthook only observes)
        return spawn(runner, name=f"src-{self.name}-{self._uid}")

    def run(self, session: Session) -> None:
        raise NotImplementedError

    # -- helpers ------------------------------------------------------------
    def row_to_engine(self, values: dict, seq: int) -> tuple[Pointer, tuple]:
        names = self.schema.column_names()
        pkeys = self.schema.primary_key_columns()
        dtypes = self.schema._dtypes()
        row = tuple(
            dt.coerce_value(values.get(n), dtypes[n]) for n in names
        )
        if pkeys:
            key = hash_values(*[values.get(k) for k in pkeys])
        else:
            key = hash_values("src", self._uid, seq)
        return key, row


def apply_connector_policy(source: DataSource, kwargs: dict,
                           policy=None) -> DataSource:
    """Attach the ``connector_policy=`` kwarg every connector ``read()``
    documents (README "Fault tolerance") to its DataSource. Central so a
    policy passed to a connector whose signature absorbs it into
    ``**kwargs`` is honored, never silently swallowed."""
    if policy is None:
        policy = kwargs.pop("connector_policy", None)
    if policy is not None:
        source.connector_policy = policy
    return source


# live CollectSessions (weak: dies with the read that created it) —
# engine.streaming.stop_all() stops these too, so a static-mode connector
# sleeping between polls cannot outlive a process-level teardown
_LIVE_COLLECT_SESSIONS: "weakref.WeakSet[CollectSession]" = weakref.WeakSet()


def stop_collect_sessions() -> None:
    """Request stop on every live CollectSession (teardown path, called
    from engine.streaming.stop_all)."""
    for cs in list(_LIVE_COLLECT_SESSIONS):
        cs.stopping.set()


class CollectSession:
    """Session double folding pushed diffs into final state — shared by
    connectors' static modes (debezium, deltalake, pyfilesystem)."""

    closed = False

    def __init__(self):
        self.state: dict = {}
        self.counts: dict = {}
        # honored by sleep()/stop_requested so a static-mode connector
        # polling through this double cannot outlive teardown
        self.stopping = threading.Event()
        _LIVE_COLLECT_SESSIONS.add(self)

    @property
    def stop_requested(self) -> bool:
        return self.stopping.is_set()

    def sleep(self, seconds: float) -> bool:
        return not self.stopping.wait(seconds)

    def push(self, key, row, diff=1, offset=None):
        c = self.counts.get(key, 0) + diff
        self.counts[key] = c
        if c > 0:
            self.state[key] = row
        else:
            self.state.pop(key, None)
            self.counts.pop(key, None)


class CallbackSource(DataSource):
    """Wraps a generator function yielding dict rows."""

    def __init__(self, fn: Callable, schema, autocommit_duration_ms=1500,
                 name="callback"):
        super().__init__(schema, autocommit_duration_ms)
        self.fn = fn
        self.name = name

    def run(self, session: Session) -> None:
        seq = 0
        for values in self.fn():
            key, row = self.row_to_engine(values, seq)
            session.push(key, row, 1)
            seq += 1
