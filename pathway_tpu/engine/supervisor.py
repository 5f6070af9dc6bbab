"""Connector supervision: retry/backoff restarts, failure escalation, and
the stall watchdog for the streaming runtime.

Rebuild of the reference engine's treatment of connector failure as
first-class (src/connectors/mod.rs — per-connector input threads whose
death is observed by the main loop): each streaming source runs under a
:class:`ConnectorSupervisor` entry that distinguishes clean end-of-stream
from a crash (``Session.closed_reason``), restarts crashed readers per a
:class:`ConnectorPolicy` with the shared backoff schedule
(internals/retries.py), and — when the retry budget is exhausted — either
terminates the whole runtime re-raising the connector's exception
(``terminate_on_error=True``) or marks the source failed-but-complete and
keeps the rest of the pipeline serving (``terminate_on_error=False``,
failure recorded in the global ErrorLog).

Restarts compose with persistence (engine/persistence.py): the supervisor
counts every entry the reader pushed past its proxy and drops exactly that
prefix from the restarted reader's re-emission, so a restart never
double-delivers — the same replay+skip protocol ``attach_source`` uses for
process restarts, applied in-process. Sources that ``seek`` on attach
re-emit from their seek base, which the per-attempt counter also covers.
Like that protocol, the skip is exact while re-emission is prefix-stable;
input that mutates during the backoff window is best-effort (warned).

The :class:`Watchdog` is a small daemon thread that detects the two hangs
a crash cannot explain: a commit loop that stops progressing (tick
deadline) and a reader that stops producing while claiming liveness (no
push / ``session.sleep`` heartbeat within the stall timeout). Reader
stalls are escalated through the normal failure path — abandon the hung
thread, restart under the policy, then terminate_on_error semantics —
so the watchdog gate actually bites instead of only logging.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass

from pathway_tpu.internals.retries import (AsyncRetryStrategy,
                                           ExponentialBackoffRetryStrategy,
                                           NoRetryStrategy)

logger = logging.getLogger(__name__)

# entry lifecycle states
RUNNING = "running"     # reader thread live (or not yet observed dead)
BACKOFF = "backoff"     # crashed; restart scheduled at next_restart_at
FAILED = "failed"       # retry budget exhausted; escalated
DONE = "done"           # clean end-of-stream
DETACHED = "detached"   # never started here (replay-only / non-reader peer)


class ConnectorStalledError(RuntimeError):
    """A reader stopped producing while claiming liveness, or hit its
    connect timeout, and the retry budget could not recover it."""


class ConnectorPolicy:
    """Restart/escalation policy for one streaming source.

    ``max_retries`` bounds the number of RESTARTS (the initial run is not a
    retry; ``max_retries=0`` escalates on the first crash). The
    ``retry_strategy`` supplies the backoff schedule via
    ``delay_for_attempt`` — its own ``max_retries`` field is ignored here.
    ``connect_timeout`` (seconds) bounds how long a freshly (re)started
    reader may stay silent — no push, no ``sleep`` heartbeat, no close —
    before the attempt counts as failed.
    """

    def __init__(self, max_retries: int = 3,
                 retry_strategy: AsyncRetryStrategy | None = None,
                 connect_timeout: float | None = None):
        if isinstance(retry_strategy, NoRetryStrategy):
            max_retries = 0
        self.max_retries = max_retries
        self.retry_strategy = retry_strategy or ExponentialBackoffRetryStrategy(
            initial_delay_ms=1000, backoff_factor=2.0, max_delay_ms=30_000)
        self.connect_timeout = connect_timeout

    def __repr__(self) -> str:
        return (f"ConnectorPolicy(max_retries={self.max_retries}, "
                f"retry_strategy={type(self.retry_strategy).__name__}, "
                f"connect_timeout={self.connect_timeout})")


@dataclass
class WatchdogConfig:
    """Stall detection deadlines (seconds). ``tick_deadline_s`` bounds the
    commit loop's inter-tick gap — the default is deliberately generous
    (5 min) because a single slow-but-healthy batch (first-tick JAX
    compilation, a huge drain) must not flip ``/healthz`` to 503 under a
    liveness probe; tighten it per deployment. ``reader_stall_timeout_s``
    (opt-in — sources that legitimately block in user code without
    heartbeating would false-positive) bounds a running reader's
    silence."""

    tick_deadline_s: float | None = 300.0
    reader_stall_timeout_s: float | None = None
    poll_interval_s: float | None = None

    def effective_poll_interval(self) -> float:
        if self.poll_interval_s is not None:
            return self.poll_interval_s
        deadlines = [d for d in (self.tick_deadline_s,
                                 self.reader_stall_timeout_s)
                     if d is not None]
        if not deadlines:
            return 1.0
        return min(1.0, max(0.02, min(deadlines) / 4))


class _SupervisedSession:
    """Reader-facing session for ONE run attempt of a supervised source.

    Duck-types io._datasource.Session. Forwards pushes to the runtime's
    session (or persistence's recording proxy), skipping the first ``skip``
    entries after a restart (the prefix the previous attempts already
    delivered). Records liveness for the watchdog on every push/sleep.
    Once ``detached`` (attempt abandoned: hung reader, connect timeout) it
    drops everything, so a zombie thread can never push into a pipeline
    that moved on without it.
    """

    def __init__(self, entry: "_SupervisedSource", inner, skip: int):
        self._entry = entry
        self._inner = inner
        self._skip = skip
        self.detached = False
        # serializes delivery against detach: _abandon must not return
        # while a push is in flight past the detached check, or the zombie
        # row lands after the restart snapshotted its skip count
        # (double-delivery). Uncontended on the hot path.
        from pathway_tpu.engine.locking import create_lock

        self._lock = create_lock("_SupervisedSession._lock")
        self.closed = threading.Event()
        self.closed_reason: str | None = None
        self.error: BaseException | None = None
        self.stopping = threading.Event()
        if inner.stopping.is_set():
            self.stopping.set()
        self.recorder = getattr(inner, "recorder", None)
        # the source's QoS backpressure flag, for a reader that waits on
        # something other than sleep() (io/fs's watched mode)
        self.backpressure = entry.backpressure

    @property
    def stop_requested(self) -> bool:
        return self.stopping.is_set()

    def sleep(self, seconds: float) -> bool:
        # detached first: a zombie attempt heartbeating through the shared
        # entry would mask a genuinely hung replacement attempt from the
        # watchdog (and falsify the connect-timeout baseline) forever
        if not self.detached:
            self._entry.touch()
            if self._entry.backpressure.is_set():
                # QoS deferral backpressure: stretch the producer's poll
                # interval while the controller is protecting query
                # latency (engine/qos.py; stop still wakes immediately)
                seconds = seconds \
                    * self._entry.supervisor.backpressure_factor
        return not self.stopping.wait(seconds)

    def push(self, key, row, diff: int = 1, offset=None) -> None:
        with self._lock:
            if self.detached:
                return
            self._entry.touch()
            if self._skip > 0:
                self._skip -= 1
                return
            self._inner.push(key, row, diff, offset=offset)
            self._entry.forwarded += 1

    def drain(self) -> list:
        return self._inner.drain()

    def close(self, reason: str = "eos",
              error: BaseException | None = None) -> None:
        if self.detached:
            return
        if not self.closed.is_set():
            self.closed_reason = reason
            self.error = error
        self.closed.set()


class _SupervisedSource:
    """Supervision state for one streaming source across restarts."""

    def __init__(self, supervisor, node, datasource, session, live_session,
                 policy: ConnectorPolicy, name: str):
        self.supervisor = supervisor
        self.node = node
        self.datasource = datasource
        self.session = session            # the session the runtime drains
        self.live_session = live_session  # what readers push into (may be
        #                                   persistence's recording proxy)
        self.policy = policy
        self.name = name
        self.state = DETACHED
        self.restarts = 0
        self.last_restart_at: float | None = None  # monotonic, stamped on
        #                                            every restart attempt
        self.forwarded = 0  # entries delivered past the proxy, all attempts
        self.stall_count = 0
        self.stalled = False
        # set by the watchdog THREAD, consumed by poll() on the commit
        # loop: an Event, not a bare bool — set/clear/is_set make the
        # cross-thread hand-off explicit (PWT202's fix shape)
        self.stall_flag = threading.Event()
        self.last_error: BaseException | None = None
        self.attempt: _SupervisedSession | None = None
        self.attempt_started_at: float | None = None
        self.last_activity: float | None = None
        # explicit boolean rather than comparing last_activity against
        # attempt_started_at: float equality on a coarse monotonic clock
        # could alias a real first push with "no activity yet"
        self.saw_activity = False
        self.next_restart_at: float | None = None
        self.threads: list[threading.Thread] = []
        # QoS backpressure (engine/qos.py): raised by the supervisor while
        # the controller is deferring this source's ingest — the reader's
        # sleep() stretches so the producer slows at its own cadence. An
        # Event (not a bare bool) for the same PWT202 reason as stall_flag:
        # the commit loop sets it, the reader thread reads it.
        self.backpressure = threading.Event()

    def touch(self) -> None:
        self.last_activity = time.monotonic()
        self.saw_activity = True


class ConnectorSupervisor:
    """Owns every streaming reader thread of one runtime. The runtime calls
    :meth:`poll` once per commit tick; all state transitions happen there
    (single-threaded), the watchdog thread only raises flags."""

    def __init__(self, *, terminate_on_error: bool = True,
                 default_policy: ConnectorPolicy | None = None):
        self.terminate_on_error = terminate_on_error
        self.default_policy = default_policy or ConnectorPolicy()
        self.entries: list[_SupervisedSource] = []
        self.fatal_error: BaseException | None = None
        self.commit_stalled = False  # set/cleared by the watchdog
        # engine-side failure absorbed by the degrade path
        # (terminate_on_error=False): a poisoned device leg or exhausted
        # persistence write retries — serving stopped cleanly but the run
        # must read as degraded, never healthy
        self.engine_failed = False
        self._stopping = False
        # QoS backpressure stretch applied to reader sleeps while the
        # flag is up (engine/qos.py; set by the runtime from QosConfig)
        self.backpressure_factor = 4.0
        # flight recorder (engine/flight_recorder.py), set by the runtime:
        # stall escalations embed its tail so a ConnectorStalledError
        # names what the engine was executing, not just the silent source
        self.recorder = None
        # crash accounting starts at THIS run: a thread that died in a
        # previous run of a long-lived process must not degrade this one
        from pathway_tpu.engine.threads import crash_epoch

        self._crash_epoch = crash_epoch()

    def _stall_error(self, msg: str) -> "ConnectorStalledError":
        rec = self.recorder
        if rec is not None and rec.enabled:
            tail = rec.dump_tail()
            if tail:
                msg += f"\nflight recorder tail:\n{tail}"
        return ConnectorStalledError(msg)

    # -- registration ------------------------------------------------------
    def add_source(self, node, datasource, session, live_session,
                   name: str | None = None) -> _SupervisedSource:
        policy = getattr(datasource, "connector_policy", None) \
            or self.default_policy
        if name is None:
            name = getattr(datasource, "persistent_id", None) \
                or f"{datasource.name}-{datasource._uid}"
        entry = _SupervisedSource(self, node, datasource, session,
                                  live_session, policy, str(name))
        self.entries.append(entry)
        return entry

    def apply_backpressure(self, active: bool) -> None:
        """Raise/clear QoS deferral backpressure on every INGEST source
        (serving sources — those carrying a request tracker slot — are
        the traffic the controller protects, never throttled here).
        Called by the commit loop each tick (engine/qos.py); readers
        observe it at their next sleep()."""
        for entry in self.entries:
            if hasattr(entry.datasource, "request_tracker"):
                continue
            if active:
                entry.backpressure.set()
            else:
                entry.backpressure.clear()

    def start_all(self) -> None:
        for entry in self.entries:
            if entry.state == DETACHED:
                self._start_attempt(entry, skip=0)

    def _start_attempt(self, entry: _SupervisedSource, skip: int) -> None:
        proxy = _SupervisedSession(entry, entry.live_session, skip)
        entry.attempt = proxy
        entry.stalled = False
        entry.stall_flag.clear()
        now = time.monotonic()
        entry.attempt_started_at = now
        entry.last_activity = now
        entry.saw_activity = False
        if entry.restarts:  # a restart, not the initial attach
            entry.last_restart_at = now
        # state flips last: the watchdog only inspects RUNNING entries, so
        # ordering (timestamps first) keeps it from reading a fresh attempt
        # against the previous attempt's last_activity
        entry.state = RUNNING
        thread = entry.datasource.start(proxy)
        entry.threads.append(thread)

    # -- per-tick state machine -------------------------------------------
    def poll(self) -> BaseException | None:
        """Advance every entry's lifecycle; returns the fatal error once an
        escalation under ``terminate_on_error=True`` demands shutdown."""
        now = time.monotonic()
        for entry in self.entries:
            if entry.state == RUNNING:
                self._poll_running(entry, now)
            elif entry.state == BACKOFF:
                if not self._stopping and now >= entry.next_restart_at:
                    entry.restarts += 1
                    # sources that resume from externally-tracked offsets
                    # (restart_resumes=True, e.g. a Kafka consumer group)
                    # re-emit nothing on restart — skipping would silently
                    # drop that many FRESH rows
                    resumes = getattr(entry.datasource, "restart_resumes",
                                      False)
                    skip = 0 if resumes else entry.forwarded
                    logger.info(
                        "restarting source %r (restart %d/%d, skipping %d "
                        "already-delivered entries)", entry.name,
                        entry.restarts, entry.policy.max_retries, skip)
                    if skip and entry.restarts == 1:
                        # same contract as persistence's prefix-replay
                        # resume (attach_source): exact only while the
                        # reader re-emits the identical prefix on restart
                        # (e.g. the source's underlying data did not
                        # mutate between the crash and the restart)
                        logger.warning(
                            "restarting source %r with the prefix-skip "
                            "protocol: the reader is assumed to re-emit "
                            "the identical first %d entries on restart; "
                            "input mutated in the backoff window may be "
                            "dropped or double-applied.",
                            entry.name, skip)
                    self._start_attempt(entry, skip=skip)
        return self.fatal_error

    def _poll_running(self, entry: _SupervisedSource, now: float) -> None:
        attempt = entry.attempt
        if attempt.closed.is_set():
            if attempt.closed_reason == "error":
                self._on_failure(entry, attempt.error, now)
            else:
                entry.state = DONE
                entry.session.close(reason="eos")
            return
        if entry.stall_flag.is_set():
            entry.stall_flag.clear()
            self._abandon(entry)
            self._on_failure(entry, self._stall_error(
                f"source {entry.name!r} stopped producing while claiming "
                f"liveness (no push/heartbeat for "
                f"{now - entry.last_activity:.1f}s)"), now)
            return
        if (entry.policy.connect_timeout is not None
                and not entry.saw_activity
                and now - entry.attempt_started_at
                > entry.policy.connect_timeout):
            self._abandon(entry)
            self._on_failure(entry, self._stall_error(
                f"source {entry.name!r} produced nothing within its "
                f"connect_timeout ({entry.policy.connect_timeout}s)"), now)

    def _abandon(self, entry: _SupervisedSource) -> None:
        """Give up on the current attempt's thread without joining it (a
        hung thread cannot be joined); detach its proxy so late pushes
        from the zombie are dropped, and ask it to stop."""
        attempt = entry.attempt
        if attempt is not None:
            with attempt._lock:  # waits out any in-flight push first
                attempt.detached = True
            attempt.stopping.set()

    def _on_failure(self, entry: _SupervisedSource, error, now: float) -> None:
        if isinstance(error, ConnectorStalledError):
            entry.stalled = True
            entry.stall_count += 1
        entry.last_error = error
        if not self._stopping and entry.restarts < entry.policy.max_retries:
            delay = entry.policy.retry_strategy.delay_for_attempt(
                entry.restarts)
            entry.next_restart_at = now + delay
            entry.state = BACKOFF
            logger.warning(
                "source %r reader failed (%s: %s); restart %d/%d in %.2fs",
                entry.name, type(error).__name__, error, entry.restarts + 1,
                entry.policy.max_retries, delay)
            return
        if self._stopping:
            # a reader crashing because teardown yanked its resources out
            # from under it is shutdown noise, not a permanent source
            # failure — no error-log entry, no misleading escalation line
            entry.state = FAILED
            entry.session.close(reason="error", error=error)
            logger.debug("source %r reader errored during teardown: %s: %s",
                         entry.name, type(error).__name__, error)
            return
        # retry budget exhausted: escalate
        entry.state = FAILED
        from pathway_tpu.internals.error import global_error_log

        global_error_log().log(
            f"connector {entry.name!r} failed after {entry.restarts} "
            f"restart(s): {type(error).__name__}: {error}",
            operator=f"source:{entry.name}", kind="connector")
        if self.terminate_on_error:
            logger.error(
                "source %r failed permanently; terminating the runtime "
                "(terminate_on_error=True)", entry.name)
            if self.fatal_error is None:
                self.fatal_error = error if error is not None else \
                    RuntimeError(f"connector {entry.name!r} failed")
        else:
            logger.error(
                "source %r failed permanently; continuing without it "
                "(terminate_on_error=False)", entry.name)
        # failed-but-complete: close the runtime-facing session so the rest
        # of the pipeline can finish and shut down cleanly — but through a
        # close() that records the error, never a clean end-of-stream
        entry.session.close(reason="error", error=error)

    # -- teardown ----------------------------------------------------------
    def request_stop(self) -> None:
        self._stopping = True
        for entry in self.entries:
            if entry.attempt is not None:
                entry.attempt.stopping.set()

    def all_threads(self) -> list[threading.Thread]:
        return [t for e in self.entries for t in e.threads]

    # -- observability (StatsMonitor / http_server) ------------------------
    def summary(self) -> list[dict]:
        now = time.monotonic()
        out = []
        for e in self.entries:
            out.append({
                "source": e.name,
                "state": e.state,
                "restarts": e.restarts,
                "last_restart_age_s": (round(now - e.last_restart_at, 1)
                                       if e.last_restart_at is not None
                                       else None),
                "forwarded": e.forwarded,
                "stalled": e.stalled,
                "stall_count": e.stall_count,
                # first line only: stall errors carry a multi-line flight
                # recorder tail that belongs in logs, not a status row
                "error": (f"{type(e.last_error).__name__}: {e.last_error}"
                          .splitlines()[0]
                          if e.last_error is not None else None),
            })
        return out

    def healthy(self) -> bool:
        """The single definition of not-degraded, consumed by /healthz:
        no escalated fatal, no stalled commit loop, no absorbed engine
        failure, no failed or stalled source, and no engine thread dead of
        an uncaught exception (engine/threads.py excepthook — a run whose
        watchdog or bridge worker silently died must not read healthy)."""
        from pathway_tpu.engine.threads import crashed_threads

        return (self.fatal_error is None and not self.commit_stalled
                and not self.engine_failed
                and not crashed_threads(self._crash_epoch)
                and not any(e.state == FAILED or e.stalled
                            for e in self.entries))


class Watchdog:
    """Daemon thread detecting a stalled commit loop and hung readers.

    Reads ``runtime.last_tick_at`` (stamped by the commit loop each
    iteration) against ``tick_deadline_s``; a breach sets
    ``supervisor.commit_stalled`` (surfaced by ``/healthz`` as 503) and
    logs — the loop itself is the hung party, so detection is all that is
    possible. Hung readers (``reader_stall_timeout_s``) are flagged on
    their supervisor entry; the commit loop's next ``poll()`` escalates
    through the normal abandon/restart/terminate path.
    """

    def __init__(self, runtime, supervisor: ConnectorSupervisor,
                 config: WatchdogConfig | None = None):
        self.runtime = runtime
        self.supervisor = supervisor
        self.config = config or WatchdogConfig()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._tick_logged = False
        # distinct commit-stall breaches over the watchdog's lifetime
        # (tests assert a legitimately-waiting commit loop never breaches;
        # commit_stalled alone clears itself on recovery)
        self.commit_stall_events = 0
        # snapshot-age breaches: a wedged snapshot loop is NOT a wedged
        # commit loop (commits keep trailing the watermark while the
        # checkpoint tier silently stops bounding recovery time)
        self.snapshot_stall_events = 0
        self._snapshot_logged = False

    def _postmortem(self) -> str:
        """The flight-recorder tail (last ticks + in-flight leg with its
        operator and user frame), or '' when nothing is recording — the
        attribution block every watchdog fire appends to its log line."""
        rec = getattr(self.runtime.scheduler, "recorder", None)
        if rec is None or not rec.enabled:
            return ""
        tail = rec.dump_tail()
        return f"\nflight recorder tail:\n{tail}" if tail else ""

    def start(self) -> None:
        from pathway_tpu.engine.threads import spawn

        self._thread = spawn(self._run, name="watchdog")

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def _run(self) -> None:
        interval = self.config.effective_poll_interval()
        while not self._stop.wait(interval):
            now = time.monotonic()
            self._check_commit_loop(now)
            self._check_readers(now)
            self._check_snapshot_age()

    def _check_commit_loop(self, now: float) -> None:
        deadline = self.config.tick_deadline_s
        if deadline is None:
            return
        last = getattr(self.runtime, "last_tick_at", None)
        if last is None:
            return
        if now - last > deadline:
            self.supervisor.commit_stalled = True
            if not self._tick_logged:
                self._tick_logged = True
                self.commit_stall_events += 1
                # the oldest unresolved device leg is the prime suspect:
                # the commit loop stamps progress on every watermark
                # advance, so a breach means the frontier itself froze.
                # bridge_inflight() survives recording-off; the flight
                # recorder tail (when on) adds the operator + user frame.
                leg = ""
                sched = getattr(self.runtime, "scheduler", None)
                inflight = sched.bridge_inflight() \
                    if hasattr(sched, "bridge_inflight") else None
                if inflight is not None:
                    leg = (f"; oldest unresolved device leg: tick "
                           f"{inflight['tick']}, in flight for "
                           f"{inflight['since_s']}s")
                logger.error(
                    "watchdog: commit loop has not ticked for %.1fs "
                    "(deadline %.1fs) — the scheduler step or a cluster "
                    "exchange is stuck%s%s", now - last, deadline, leg,
                    self._postmortem())
        elif self.supervisor.commit_stalled:
            self.supervisor.commit_stalled = False
            self._tick_logged = False
            logger.warning("watchdog: commit loop progressing again")

    def _check_snapshot_age(self) -> None:
        """Warn when the operator-state snapshot tier stops keeping pace:
        age beyond 3x the configured tick cadence means restarts are
        quietly drifting back toward O(history) replay even though the
        commit loop itself is healthy."""
        tick_cadence = getattr(self.runtime, "_snapshot_every_ticks", 0)
        byte_cadence = getattr(self.runtime, "_snapshot_every_bytes", 0)
        persistence = getattr(self.runtime, "persistence", None)
        if (not tick_cadence and not byte_cadence) or persistence is None:
            return
        if persistence.wal_entries_uncovered == 0:
            # idle stream: no durable entry lies beyond the last
            # generation, so there is nothing a snapshot SHOULD have
            # covered — age grows harmlessly (ticks are free)
            if self._snapshot_logged:
                self._snapshot_logged = False
                logger.info("watchdog: snapshot cadence recovered")
            return
        if tick_cadence:
            lag = (persistence.last_commit_tick
                   - persistence.last_snapshot_tick)
            breach = lag > 3 * tick_cadence
            unit, cadence = "ticks", tick_cadence
        else:
            lag = persistence.wal_bytes_since_snapshot
            breach = lag > 3 * byte_cadence
            unit, cadence = "bytes", byte_cadence
        if breach:
            if not self._snapshot_logged:
                self._snapshot_logged = True
                self.snapshot_stall_events += 1
                logger.warning(
                    "watchdog: operator-state snapshot age is %d %s "
                    "(cadence %d, threshold %d) — the snapshot pass is "
                    "wedged or disabled while commits keep flowing; "
                    "restart time is growing with history again",
                    lag, unit, cadence, 3 * cadence)
        elif self._snapshot_logged:
            self._snapshot_logged = False
            logger.info("watchdog: snapshot cadence recovered")

    def _check_readers(self, now: float) -> None:
        timeout = self.config.reader_stall_timeout_s
        if timeout is None:
            return
        for entry in self.supervisor.entries:
            if entry.state != RUNNING or entry.stall_flag.is_set():
                continue
            attempt = entry.attempt
            if attempt is None or attempt.closed.is_set() \
                    or attempt.stopping.is_set():
                continue
            if entry.threads and not entry.threads[-1].is_alive():
                continue  # thread death is the supervisor's poll to observe
            if entry.last_activity is not None \
                    and now - entry.last_activity > timeout:
                logger.error(
                    "watchdog: source %r claims liveness but produced no "
                    "push/heartbeat for %.1fs (stall timeout %.1fs)%s",
                    entry.name, now - entry.last_activity, timeout,
                    self._postmortem())
                entry.stall_flag.set()
