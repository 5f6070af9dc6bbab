"""Realtime microbatch runtime.

Replaces the reference's worker hot loop (dataflow.rs:5519-5572 —
``loop { probers; flushers; pollers; step_or_park }``): connector threads
feed sessions; every autocommit interval the runtime drains all sessions,
advances the logical timestamp, and runs one scheduler step. Totally-ordered
timestamps + whole-batch steps give the same consistency guarantee as
timely's progress frontiers (every time is complete when processed).
"""

from __future__ import annotations

import threading
import time as _time

import weakref

from pathway_tpu.engine.delta import Delta
from pathway_tpu.engine.graph import Scheduler
from pathway_tpu.internals.monitoring import MonitoringLevel, StatsMonitor
from pathway_tpu.testing import faults

# live runtimes (weak: a runtime dies with its last strong ref). Lets
# embedding code — and the test harness — stop pw.run() loops started on
# background threads: stop_all() requests stop and joins reader threads.
_ACTIVE_RUNTIMES: "weakref.WeakSet[StreamingRuntime]" = weakref.WeakSet()


def live_runtimes() -> list["StreamingRuntime"]:
    """The StreamingRuntimes alive in this process — for in-process reads
    of a server started on a background thread (scheduler, bridge stats)."""
    return list(_ACTIVE_RUNTIMES)


def stop_all(join_timeout: float = 5.0) -> None:
    """Request stop on every live StreamingRuntime and join their reader
    threads; also stops static-mode connectors sleeping between polls
    (CollectSession). Safe to call from any thread; idempotent."""
    from pathway_tpu.io._datasource import stop_collect_sessions

    stop_collect_sessions()
    for rt in list(_ACTIVE_RUNTIMES):
        rt.stop()
    for rt in list(_ACTIVE_RUNTIMES):
        rt.join_readers(join_timeout)


def _is_serving(datasource) -> bool:
    """A source of requests (``rest_connector``): it declares the
    ``request_tracker`` slot. Its rows are never clipped by an ingest
    budget, and a request pushed into its session wakes the commit loop."""
    return hasattr(datasource, "request_tracker")


class StreamingRuntime:
    def __init__(self, runner, *, monitoring_level=None, with_http_server=False,
                 persistence_config=None, terminate_on_error=True,
                 default_commit_ms: int = 100, n_workers: int | None = None,
                 cluster=None, connector_policy=None, watchdog=None,
                 trace_path: str | None = None, replica=None, qos=None):
        from pathway_tpu.engine.supervisor import ConnectorSupervisor
        from pathway_tpu.engine.threads import install_excepthook
        from pathway_tpu.io._datasource import Session

        # read-replica mode (engine/replica.py): hydrate from the
        # primary's snapshot + WAL suffix through a READ-ONLY driver and
        # tail the durability log instead of reading persisted feeds
        # live; serving sources (rest routes) still run. Mutually
        # exclusive with owning the persistence root or clustering.
        self.replica = replica
        if replica is not None:
            if persistence_config is not None:
                raise ValueError(
                    "a replica cannot own a persistence root: it tails "
                    "the PRIMARY's root read-only (drop "
                    "persistence_config, or drop replica_of)")
            if cluster is not None:
                raise ValueError(
                    "replica mode is single-process (scale out by adding "
                    "replicas behind the router, not cluster workers)")
        self.role = "replica" if replica is not None else "primary"

        # uncaught exceptions in ANY engine thread land in the ErrorLog
        # and flip /healthz instead of dying silently on stderr
        install_excepthook()

        if n_workers is None:
            from pathway_tpu.internals.config import get_pathway_config

            n_workers = get_pathway_config().threads
        self.runner = runner
        self.cluster = cluster
        self.default_commit_ms = default_commit_ms
        self.terminate_on_error = terminate_on_error
        self._stop = threading.Event()
        # what the commit loop sleeps on between ticks: set by a stop
        # request and by a request pushed into a serving source's session
        # (wired below), so the autocommit period is the longest a
        # request waits for a tick and not what it waits on average
        self._wake = threading.Event()
        # ticks run, by what ended the loop's wait (/metrics
        # pathway_tpu_ticks_total; the ``tick`` span's ``woken_by``)
        self.ticks_woken_by = {"period": 0, "request": 0}
        # a request is waiting for the leg in flight to retire: the
        # bridge's worker ends the loop's wait then (a bare bool store,
        # atomic under the GIL, as ``last_tick_at``)
        self._wake_on_retire = False
        # last tick run_time RETURNED for (pipelined: its device leg may
        # still be in flight — the bridge watermark, not this counter, is
        # the durability frontier)
        self._last_completed_tick = 0
        # an engine failure swallowed by the degrade path
        # (terminate_on_error=False): kept so teardown neither re-raises
        # it nor mistakes it for an unobserved device error
        self._degraded_engine_error = None
        self.monitor = StatsMonitor(monitoring_level or MonitoringLevel.NONE)
        # QoS control plane (engine/qos.py): resolved FIRST because an
        # armed controller needs the measurement plane — QoS implies the
        # flight recorder (and with it the request tracker)
        from pathway_tpu.engine.qos import resolve_qos

        self._qos_config = resolve_qos(qos)
        if self._qos_config is not None and cluster is not None:
            raise ValueError(
                "QoS is single-process (the controller partitions ONE "
                "device's time; scale out with replicas behind the "
                "router, each running its own controller)")
        self.qos = None
        # flight recorder (engine/flight_recorder.py): on when a trace
        # path is configured or the data is observable (http server /
        # live dashboard), or when QoS needs the request tracker;
        # otherwise None — one dead branch per op step
        from pathway_tpu.engine.flight_recorder import FlightRecorder

        self.recorder = FlightRecorder.from_env(
            trace_path=trace_path,
            auto_on=(with_http_server or self.monitor.enabled()
                     or self._qos_config is not None))
        if self.recorder is not None:
            # fleet identity on the trace (engine/fleet_observability.py):
            # the merged Perfetto timeline names each process's track by
            # role + process label, and replicas share the id the router
            # knows them by
            import os as _os

            self.recorder.role = self.role
            self.recorder.process = (
                replica.replica_id if replica is not None
                else _os.environ.get("PATHWAY_REPLICA_ID")
                or f"primary-{_os.getpid()}")
        # continuous profiler (engine/profiler.py): same observability
        # arming rule as the recorder; installed process-wide so the
        # kernel cost-model hooks and the bridge's leg context find it
        # with one global load. Sampling starts at run().
        from pathway_tpu.engine.profiler import (Profiler, current_profiler,
                                                 install_profiler)

        self.profiler = Profiler.from_env(
            auto_on=(with_http_server or self.monitor.enabled()
                     or self._qos_config is not None))
        self._installed_profiler = False
        if self.profiler is not None and current_profiler() is None:
            install_profiler(self.profiler)
            self._installed_profiler = True
        self.scheduler = Scheduler(runner.graph, n_workers=n_workers,
                                   cluster=cluster, recorder=self.recorder)
        # watchdog progress on every resolved device leg: the commit loop
        # may legitimately block in submit() behind a full in-flight
        # window — a slow-but-ADVANCING watermark is progress, not a
        # stall; only a frozen one may breach the tick deadline
        self.scheduler.set_watermark_listener(self._on_watermark_advance)
        self.sessions = []
        # supervision: reader threads are owned by the supervisor, which
        # restarts crashed readers per policy and escalates per
        # terminate_on_error (engine/supervisor.py)
        self.supervisor = ConnectorSupervisor(
            terminate_on_error=terminate_on_error,
            default_policy=connector_policy)
        self.supervisor.recorder = self.recorder
        self.monitor.set_supervisor(self.supervisor)
        self.watchdog_config = watchdog
        self.watchdog = None
        # stamped by the commit loop each iteration; the watchdog measures
        # tick progress against this
        self.last_tick_at = _time.monotonic()
        self.persistence = None
        # operator-state snapshot cadence (0 = disabled): env knobs win
        # over the Config fields; single-process only (a cluster's state
        # is split across processes — no consistent single-file cut yet)
        self._snapshot_every_ticks = 0
        self._snapshot_every_bytes = 0
        if persistence_config is not None and persistence_config.backend is not None:
            from pathway_tpu.engine.persistence import PersistenceDriver

            self.persistence = PersistenceDriver(persistence_config)
            # dashboard durability panel: watermark lag is visible live
            self.monitor.persistence = self.persistence
            if cluster is None:
                from pathway_tpu.internals.config import _env_int

                self._snapshot_every_ticks = max(0, _env_int(
                    "PATHWAY_SNAPSHOT_EVERY_TICKS",
                    int(getattr(persistence_config, "snapshot_every_ticks",
                                0) or 0)))
                self._snapshot_every_bytes = max(0, _env_int(
                    "PATHWAY_SNAPSHOT_EVERY_BYTES",
                    int(getattr(persistence_config, "snapshot_every_bytes",
                                0) or 0)))
                if self._snapshots_enabled() \
                        and not self.persistence.snapshots_supported:
                    # never run the (expensive) state-capture pass just
                    # to have write_snapshot discard it every cadence
                    import logging

                    logging.getLogger(__name__).warning(
                        "snapshot cadence configured but the %r "
                        "persistence backend cannot store snapshots — "
                        "recovery stays full-WAL replay",
                        self.persistence.kind)
                    self._snapshot_every_ticks = 0
                    self._snapshot_every_bytes = 0
            if self._snapshots_enabled():
                # consolidated emitted-state tracking must be on BEFORE
                # any data flows, so a later snapshot can re-emit the
                # covered prefix's visible state to fresh sinks
                self.scheduler.enable_output_tracking()
        self.http_server = None
        if with_http_server:
            from pathway_tpu.engine.http_server import MonitoringHttpServer

            self.http_server = MonitoringHttpServer(self)

        for node, datasource in runner._stream_subjects:
            session = Session()
            self.sessions.append((node, session, datasource))
            if getattr(datasource, "durable_ack", False) \
                    and self.persistence is None and replica is None:
                # a durable acknowledgement with no WAL to make it
                # durable would hold every response forever — refuse the
                # contradiction loudly instead of hanging clients
                raise ValueError(
                    "rest_connector(durable_ack=True) requires a "
                    "persistence root (the acknowledgement IS the fsync "
                    "of the request's WAL record) — configure "
                    "persistence, or drop durable_ack")
        if self.replica is not None:
            # classify sources: WAL-backed feeds are tailed (no reader
            # thread), serving sources run live
            self.replica.bind(self.sessions)
        if cluster is None:
            # a request wakes the loop; a row of an ingest source never
            # does. Under a cluster every process keeps the period: the
            # tick is a lock-step exchange (_tick_sync), and a process
            # woken alone would only block in it until its peers' period
            # ends
            for _node, session, ds in self.sessions:
                if _is_serving(ds):
                    session.wake = self._wake.set
        # fleet control channel (engine/replica.py): when a router's
        # control address is configured, this process — replica OR a
        # read-serving primary — registers and heartbeats its applied
        # tick / staleness / serving quantiles over the framed HMAC
        # transport
        from pathway_tpu.engine.replica import (ControlClient,
                                                control_address_from_env)

        self._control_client = None
        ctrl_addr = control_address_from_env()
        if ctrl_addr is not None and cluster is None:
            self._control_client = ControlClient(
                self, ctrl_addr, role=self.role,
                replica_id=(self.replica.replica_id
                            if self.replica is not None else None))
        # source index -> persistence recording proxy: the commit loop
        # drains THROUGH the proxy (seal_drain) so seals align exactly
        # with drains — the alignment operator-state snapshots require
        self._drain_proxies: dict[int, object] = {}
        # (ingest_rows, query_rows, deferred) of the latest drain — the
        # QoS feedback loop's per-tick input
        self._last_drain: tuple[int, int, bool] = (0, 0, False)
        # query rows of the request-woken ticks since the last period
        # tick: either ingest budget is fed once an interval, as before
        self._queries_between_periods = 0
        # the ingest budget with no QoS armed (engine/qos.py
        # DeviceBackpressure): made by the first tick that has a bridge
        self._backpressure = None
        # while recording, what the latest drain took for the tick's
        # spans: (rows by source name, requests picked up — a serving
        # source's insertions; its retractions are rows, not requests;
        # the budget its ingest sources were held to, None for none)
        self._last_drain_counts: tuple[dict[str, int], int, int | None] \
            = ({}, 0, None)
        # cumulative bridge exec_ms at the last QoS tick (delta = this
        # tick's resolved device time, the cost-model signal)
        self._qos_exec_ms_seen = 0.0
        # write-path failover (engine/replica.py ControlClient): a
        # ("promote", ...) control frame parks its payload here and the
        # COMMIT LOOP executes the promotion synchronously between ticks
        # — never the control thread, because promotion rewires the
        # scheduler's feeding machinery, which only the loop may touch.
        # Event, not a bare bool: set by the control thread, read by the
        # loop (PWT201).
        self._promote_event = threading.Event()
        self._promote_payload: dict = {}
        # session indexes the replica tails instead of reading live —
        # exactly the sources a promotion must start readers for
        self._tailed_sources: list[int] = []
        self.promotions = 0  # completed promotions (→ /metrics)
        self.failover_promotion_s: float | None = None
        # the tick the promoted timeline ends at — rides every heartbeat
        # so the router can re-anchor surviving replicas exactly there
        # (pending ticks PAST it are the dead primary's torn commit)
        self.promotion_tick: int | None = None

        # request-scoped serving tracing (engine/request_tracker.py):
        # sources that declare a request_tracker slot (rest_connector)
        # get the run's tracker, so each query's ingress/queue/host/
        # device/response stages are stamped end to end
        self._request_tracker = (
            self.recorder.requests if self.recorder is not None else None)
        if self._request_tracker is not None:
            for _node, _session, ds in self.sessions:
                if _is_serving(ds):
                    ds.request_tracker = self._request_tracker
        # a polling source writes its ``connector.pass`` spans through the
        # recorder slot of its session (io/_datasource.py; None = off)
        if self.recorder is not None:
            for _node, session, _ds in self.sessions:
                session.recorder = self.recorder
        # QoS controller (engine/qos.py): turns the tracker's burn rate /
        # stage p50s into per-tick ingest budgets, admission decisions
        # and coalescing accounting. Wired into every serving source's
        # admission gate; the commit loop consults it per tick.
        if self._qos_config is not None:
            if self._request_tracker is None:
                # PATHWAY_FLIGHT_RECORDER=0 force-disabled the
                # measurement plane the controller feeds on: refuse the
                # contradictory config loudly rather than run a control
                # loop with no inputs
                raise ValueError(
                    "QoS is enabled but PATHWAY_FLIGHT_RECORDER=0 "
                    "force-disabled the flight recorder — the controller "
                    "needs the request tracker's burn rate; drop one of "
                    "the two flags")
            from pathway_tpu.engine.qos import (QosController,
                                                install_controller)

            self.qos = QosController(self._qos_config,
                                     self._request_tracker)
            self.supervisor.backpressure_factor = \
                self._qos_config.backpressure_factor
            for _node, _session, ds in self.sessions:
                if hasattr(ds, "qos"):
                    ds.qos = self.qos
            install_controller(self.qos)

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        self.supervisor.request_stop()
        for _node, session, _ds in self.sessions:
            session.stopping.set()

    def request_promotion(self, payload: dict | None) -> None:
        """Control-thread entry: ask the commit loop to promote this
        replica to primary. Idempotent — a duplicate frame, or one
        delivered to a process that is already primary, is a no-op when
        the loop picks it up."""
        self._promote_payload = dict(payload or {})
        self._promote_event.set()

    def _execute_promotion(self, time_counter: int) -> int:
        """Promote this replica to primary (commit-loop thread only).

        The state machine: (1) **finish tailing** — pump until the WAL
        yields nothing new for more quiet rounds than the tailer's
        newest-tick hold-back, so every COMPLETE commit tick of the dead
        primary is applied; (2) **fence** — bump the fencing epoch and
        truncate the dead primary's incomplete final commit
        (persistence.promote): from here a resumed zombie primary's next
        write raises FencedPrimaryError; (3) **rewire** — the read-only
        driver becomes this runtime's read-write persistence, and
        connector readers start for every previously-tailed source with
        the durable prefix marked already-covered
        (attach_source(replay=False): the scheduler holds that state
        from tailing); (4) **serve** — the role flips to primary and the
        next heartbeat tells the router to send writes here. A crash
        between (2) and (3) — the ``replica.promote.crash`` fault point
        — leaves a bumped epoch and no primary: the router elects the
        next candidate, whose own promote() bumps the epoch again
        (``min_epoch`` keeps the sequence monotone)."""
        self._promote_event.clear()
        payload = self._promote_payload
        if self.replica is None or self.role == "primary":
            return time_counter  # duplicate/stale frame: no-op
        import logging

        t0 = _time.monotonic()
        tailer = self.replica
        # (1) drain the dead primary's WAL to its last complete tick
        quiet = 0
        while quiet < 5:
            before = tailer.applied_tick
            time_counter = tailer.pump(self, time_counter)
            quiet = quiet + 1 if tailer.applied_tick == before else 0
        complete_tick = tailer.applied_tick
        # (2) fence: claim the next epoch (>= the router's announced
        # one), flip the driver read-write, cut the torn tail
        max_tick, epoch = tailer.driver.promote(
            tailer.replica_id, complete_tick,
            min_epoch=int(payload.get("epoch", 0)))
        faults.hit("replica.promote.crash",
                   epoch=epoch, complete_tick=complete_tick)
        # (3) rewire: the tailer's driver IS the new persistence root
        self.persistence = tailer.driver
        self.monitor.persistence = self.persistence
        for i in self._tailed_sources:
            node, session, datasource = self.sessions[i]
            proxy = self.persistence.attach_source(
                datasource, session, replay=False)
            self._drain_proxies[i] = proxy
            self.supervisor.add_source(node, datasource, session, proxy)
        self._tailed_sources = []
        self.supervisor.start_all()  # only the newly-added entries start
        # the tailer must never pump again — it would re-apply this
        # process's OWN commits; its driver lives on as self.persistence
        # (closed once, by teardown's persistence branch)
        self.replica = None
        # (4) serve
        self.role = "primary"
        if self.recorder is not None:
            self.recorder.role = "primary"
            self.recorder.note_promotion(epoch, complete_tick)
        time_counter = max(time_counter, max_tick + 1)
        self.promotions += 1
        self.promotion_tick = complete_tick
        self.failover_promotion_s = _time.monotonic() - t0
        logging.getLogger(__name__).warning(
            "promoted to primary at fencing epoch %d (complete tick %d, "
            "max durable tick %d, %.3fs): accepting writes",
            epoch, complete_tick, max_tick, self.failover_promotion_s)
        return time_counter

    def join_readers(self, timeout: float = 5.0) -> None:
        """Join connector threads after stop(); they observe the session's
        stop event between polls (Session.sleep / stop_requested)."""
        deadline = _time.monotonic() + timeout
        for t in self.supervisor.all_threads():
            t.join(max(0.0, deadline - _time.monotonic()))

    def _on_watermark_advance(self, tick: int) -> None:
        # bridge-worker thread; a bare float store is atomic under the GIL
        self.last_tick_at = _time.monotonic()
        if self._wake_on_retire:
            # a request waits for the bridge to be free (_wait_for_tick)
            self._wake_on_retire = False
            self._wake.set()

    def _handle_engine_failure(self, error: BaseException) -> bool:
        """A failure escaped the commit loop: a poisoned device leg, a
        persistence append whose write retries were exhausted, or an
        operator error. Escalate through the supervisor's existing
        terminate-vs-degrade contract — teardown's final watermark
        commit makes the last fully-resolved prefix durable on both
        branches, so nothing unprocessed can be covered by the log
        either way. Returns True iff the failure is absorbed as a degrade
        (``terminate_on_error=False``): recorded in the global ErrorLog
        (kind="engine"), flagged on the supervisor, run ends cleanly.
        Interrupts and shutdown requests always re-raise."""
        if isinstance(error, (KeyboardInterrupt, SystemExit,
                              GeneratorExit)):
            return False
        if self.terminate_on_error:
            return False
        import logging

        from pathway_tpu.internals.error import global_error_log

        kind = ("device leg"
                if self.scheduler.take_device_error() is error
                else "engine")
        global_error_log().log(
            f"{kind} failed under terminate_on_error=False; stopping "
            f"ingestion after the last committed watermark: "
            f"{type(error).__name__}: {error}",
            operator="engine", kind="engine")
        logging.getLogger(__name__).error(
            "%s failed; degrading to a clean stop "
            "(terminate_on_error=False). Restart resumes from the last "
            "committed watermark.", kind, exc_info=error)
        self.supervisor.engine_failed = True
        self._degraded_engine_error = error
        return True

    def _commit_watermark_tick(self, tick: int) -> None:
        """One trailing checkpoint: commit the longest resolved prefix of
        device legs (<= ``tick``) WITHOUT draining the bridge — the
        pipeline keeps running ahead at full ``PATHWAY_DEVICE_INFLIGHT``
        depth while durability follows the watermark."""
        wm = self.scheduler.commit_watermark(tick)
        bridge = self.scheduler.bridge_stats()
        self.persistence.commit(
            tick, watermark=wm,
            inflight=bridge["depth"] if bridge is not None else 0)
        self._flush_durable_acks(wm)

    def _flush_durable_acks(self, watermark: int) -> None:
        """Release buffered write acknowledgements for ticks the WAL now
        covers (io/http rest_connector ``durable_ack=True``): commit()
        returned, so entries sealed <= ``watermark`` are fsynced — an
        acknowledgement released here survives SIGKILL (replayed on
        restart, tailed by every replica). Runs on the commit-loop
        thread, same as the subscribe callback that buffers."""
        for _node, _session, ds in self.sessions:
            release = getattr(ds, "on_commit_watermark", None)
            if release is not None:
                release(watermark)

    def _tick_feedback(self, tick: int, tick_ms: float,
                       commit_s: float, by_request: bool = False) -> None:
        """Close the loop for one tick: feed the ingest budget what the
        tick actually did (rows drained, host wall time, what retired on
        the bridge since the last tick). With QoS armed the budget is the
        controller's, and its deferral backpressure goes on to the
        connector readers; without, it is ``DeviceBackpressure``'s, which
        holds nothing back until a submit finds the bridge's window
        full.

        Both budgets are reckoned in commit intervals, so both are fed by
        the period's ticks alone. A tick that a request woke
        (``by_request``) looked at no ingest source and moves no bound:
        its queries are counted into the next period tick's look, which
        so sees one interval's rows, queries and device time, as when
        that tick drained them itself (an interval that served queries
        is no reading of an ingest row's cost)."""
        ingest_rows, query_rows, deferred = self._last_drain
        if by_request:
            self._queries_between_periods += query_rows
            return
        query_rows += self._queries_between_periods
        self._queries_between_periods = 0
        bridge = self.scheduler.bridge_stats()
        if self.qos is None:
            if bridge is not None:
                if self._backpressure is None:
                    from pathway_tpu.engine.qos import DeviceBackpressure

                    self._backpressure = DeviceBackpressure(commit_s)
                self._backpressure.on_tick(
                    tick, ingest_rows=ingest_rows, query_rows=query_rows,
                    deferred=deferred, bridge=bridge)
            return
        device_ms = None
        if bridge is not None:
            # cumulative resolved-leg exec time: the per-tick delta lags
            # the submitting tick by the in-flight depth, which is fine
            # for an EWMA cost model
            seen = bridge["exec_ms"]
            device_ms = max(0.0, seen - self._qos_exec_ms_seen)
            self._qos_exec_ms_seen = seen
        self.qos.on_tick(ingest_rows=ingest_rows, deferred=deferred,
                         tick_ms=tick_ms, device_ms=device_ms,
                         queries_in_tick=query_rows)
        self.supervisor.apply_backpressure(self.qos.backpressure_active)

    def _snapshots_enabled(self) -> bool:
        return bool(self._snapshot_every_ticks
                    or self._snapshot_every_bytes)

    def _snapshot_due(self, tick: int) -> bool:
        if not self._snapshots_enabled() or self.persistence is None:
            return False
        p = self.persistence
        if p.wal_entries_uncovered == 0:
            # nothing durable beyond the last generation: operator state
            # is unchanged — an idle stream must not churn generations
            return False
        if self._snapshot_every_ticks and \
                tick - p.last_snapshot_tick >= self._snapshot_every_ticks:
            return True
        return bool(self._snapshot_every_bytes
                    and p.wal_bytes_since_snapshot
                    >= self._snapshot_every_bytes)

    def _snapshot_pass(self, tick: int) -> None:
        """Operator-state checkpoint at ``tick``: wait for the bridge
        WATERMARK to reach the tick (never a full barrier — with the host
        thread parked here no later leg exists, so reaching the watermark
        IS a consistent cut at exactly ``tick``), commit everything
        sealed <= tick so the WAL covers the cut, capture operator state,
        write the snapshot generation and compact the WAL. Any failure
        (unsupported operator, unpicklable state) disables snapshots for
        the rest of the run, loudly — recovery falls back to full-WAL
        replay, never to a checkpoint with missing state."""
        from pathway_tpu.engine.operators import SnapshotUnsupported
        from pathway_tpu.engine.snapshot_sanitizer import \
            SnapshotCoverageViolation

        wm = self.scheduler.wait_watermark(tick)  # re-raises leg failures
        if wm < tick:
            return  # frozen/idle bridge: no consistent cut available
        bridge = self.scheduler.bridge_stats()
        self.persistence.commit(
            tick, watermark=tick,
            inflight=bridge["depth"] if bridge is not None else 0)
        if self.persistence.wal_entries_uncovered == 0:
            # the watermark moved but no durable entry lies beyond the
            # last generation (clean shutdown of an idle stream, teardown
            # after a quiescent tail): skip — no empty-generation churn.
            # A pure-replay restart DOES snapshot here: its replayed
            # suffix counts as uncovered, and covering it bounds the
            # NEXT restart.
            return
        try:
            payload = {
                "graph": self.scheduler.graph_fingerprint(),
                "n_workers": self.scheduler.n_workers,
                "nodes": self.scheduler.snapshot_operator_states(),
            }
            self.persistence.write_snapshot(tick, payload)
        except SnapshotUnsupported as e:
            import logging

            logging.getLogger(__name__).warning(
                "operator-state snapshots disabled for this run: %s", e)
            self._snapshot_every_ticks = 0
            self._snapshot_every_bytes = 0
        except faults.InjectedFault:
            # test-injected crash at a snapshot/compaction fault point:
            # die like any other armed point (the crash sweep simulates
            # process death here, not a degradable write failure)
            raise
        except SnapshotCoverageViolation:
            # the sanitizer found a snapshot that would restore wrong —
            # degrading to WAL replay would hide exactly the bug the
            # sanitizer exists to surface; fail the run loudly
            raise
        except Exception:
            import logging

            logging.getLogger(__name__).warning(
                "operator-state snapshot at tick %d failed; snapshots "
                "disabled for this run (recovery falls back to full-WAL "
                "replay)", tick, exc_info=True)
            self._snapshot_every_ticks = 0
            self._snapshot_every_bytes = 0

    def _restore_snapshot(self) -> int:
        """Load the newest valid snapshot (if any), restore operator
        states and re-emit the covered prefix's consolidated output state
        to the sinks. Returns the snapshot tick (0 = none)."""
        snap = self.persistence.load_snapshot()
        if snap is None:
            return 0
        payload = snap["payload"]
        if payload.get("graph") != self.scheduler.graph_fingerprint():
            raise ValueError(
                "persistence root carries an operator-state snapshot for "
                "a DIFFERENT pipeline (graph fingerprint mismatch) — the "
                "program changed between runs; clear the persistence "
                "root to start fresh")
        self.scheduler.restore_operator_states(payload["nodes"])
        self.scheduler.emit_restored_outputs(snap["tick"])
        return snap["tick"]

    def _wait_for_tick(self, period_due: float) -> str:
        """Sleep until the next commit tick and say what ended the wait:
        ``"stop"`` (a stop request: the loop ends), ``"period"`` (the
        autocommit interval ran out at ``period_due``, on ``monotonic``:
        a tick over every source) or ``"request"`` (a serving source
        pushed a request before that: a tick over the serving sources
        alone, at once, or as soon as the device leg in flight has
        retired)."""
        while not self._stop.is_set():
            timeout = period_due - _time.monotonic()
            if timeout <= 0:
                # the period's tick drains the serving sources too
                self._wake.clear()
                return "period"
            if self._wake.wait(timeout) and not self._stop.is_set():
                # cleared BEFORE the drain: a request pushed from here on
                # either rides this tick or finds the flag down and wakes
                # the next
                self._wake.clear()
                if not self._held_behind_a_leg():
                    return "request"
        return "stop"

    def _held_behind_a_leg(self) -> bool:
        """Whether a waiting request's tick has to wait for the device
        bridge: a leg submitted now would only queue behind the one in
        flight, so the loop sleeps on until that leg retires
        (:meth:`_on_watermark_advance` ends the wait) and the requests
        that arrive meanwhile ride one tick, one batch of the scan. On
        the chip, against a tick at every arrival (PERF.md, PR 34): the
        same median at 20 queries/s, and at 30/s p50 30.7 ms for 37.2,
        p95 58.7 for 82.7. The period still ends the wait."""
        # raised BEFORE the look: a leg that retires after it finds the
        # flag up and wakes the loop, which then looks again
        self._wake_on_retire = True
        if self.scheduler.bridge_depth() > 0:
            return True
        self._wake_on_retire = False
        return False

    def _drain_and_forward(self, tick: int, budgeted: bool = True,
                           serving_only: bool = False):
        """Drain local sessions; under a cluster split each source's rows
        by owning process (single reader on process 0 forwards shards —
        reference: 'single reader forwards for non-partitioned sources').
        Returns (any_data, all_closed, pushes) where pushes maps
        peer -> {source index -> entries}.

        With QoS armed (and ``budgeted``), ingest sources drain at most
        the controller's per-tick row budget (engine/qos.py); without it,
        at most what ``DeviceBackpressure`` allows while the device is the
        slower side. Clipped rows stay *in their session* and ride later
        ticks through this same path, so seals keep covering exactly what
        each tick drained — deferral moves timestamps, never durability
        or content.
        Serving sources (request-tracking) are never clipped; the
        end-of-stream re-drain passes ``budgeted=False`` (latency has no
        meaning once every source closed — finish at full throughput).

        ``serving_only``: a tick that a request woke drains the serving
        sources alone. Ingest sources are not looked at, so their rows,
        their seals and their budget keep the period's cadence: what an
        ingest source hands over per interval and per device leg is what
        it hands over with no request at all."""
        any_data = False
        all_closed = True
        tracker = self._request_tracker
        pushes: dict[int, dict[int, list]] = {}
        limiter = self.qos if self.qos is not None else self._backpressure
        budget = (limiter.ingest_row_budget()
                  if limiter is not None and budgeted else None)
        ingest_rows = 0
        query_rows = 0
        deferred = False
        rec = self.recorder
        by_source = {} if rec is not None and rec.enabled else None
        requests = 0
        n = len(self.sessions)
        # rotate the drain order of INGEST sources by tick so a tight
        # budget cannot starve whichever source happens to sit last
        order = list(range(n))
        if budget is not None and n > 1:
            r = tick % n
            order = order[r:] + order[:r]
        for i in order:
            node, session, datasource = self.sessions[i]
            serving = _is_serving(datasource)
            if serving_only and not serving:
                if not session.closed.is_set():
                    all_closed = False
                continue
            limit = None
            if budget is not None and not serving:
                limit = budget - ingest_rows
                if limit < 0:
                    limit = 0
            rec = self._drain_proxies.get(i)
            # the recording proxy drains + seals atomically: sealed <= t
            # IS drained <= t, the consistency-cut alignment snapshots
            # need (a separate seal would leak gap entries into t+1).
            # pwt-ok: PWT307 — the plain drain() arm only runs when
            # rec is None, i.e. the source is NOT persisted: there is
            # no WAL to seal against, so nothing can be lost on crash
            entries = session.drain(limit) if rec is None \
                else rec.seal_drain(tick, limit)
            if limit is not None and session.backlog() > 0 \
                    and len(entries) >= limit:
                # the budget clipped this source: the remainder rides a
                # later tick (never dropped — visible in the counters)
                deferred = True
                limiter.note_deferral(session.backlog())
            if entries:
                any_data = True
                if serving:
                    query_rows += len(entries)
                else:
                    ingest_rows += len(entries)
                if by_source is not None:
                    by_source[f"{datasource.name}-{datasource._uid}"] = \
                        len(entries)
                    if serving:
                        requests += sum(1 for _k, _row, diff in entries
                                        if diff > 0)
                if tracker is not None and \
                        getattr(datasource, "request_tracker", None) \
                        is tracker:
                    # tick-pickup stamp: ends each request's queue stage
                    tracker.picked_up(entries, tick)
                delta = Delta(entries)
                if self.cluster is not None:
                    for peer, ents in self.scheduler.partition_remote(
                            delta).items():
                        pushes.setdefault(peer, {})[i] = ents
                self.scheduler.push_source(node, delta)
            if not session.closed.is_set():
                all_closed = False
        self._last_drain = (ingest_rows, query_rows, deferred)
        if by_source is not None:
            self._last_drain_counts = (by_source, requests,
                                       None if serving_only else budget)
        return any_data, all_closed, pushes

    def _tick_sync(self, tick, any_data, all_closed, pushes):
        """Cluster barrier per commit tick: exchange forwarded source rows
        and merge liveness so all processes tick (and stop) in lockstep."""
        if self.cluster is None:
            return any_data, all_closed
        msgs = {p: {"rows": pushes.get(p), "any": any_data,
                    "closed": all_closed} for p in self.cluster.peers}
        recv = self.cluster.exchange(("tick", tick), msgs)
        for payload in recv.values():
            rows = payload.get("rows")
            if rows:
                for i, ents in rows.items():
                    node = self.sessions[i][0]
                    self.scheduler.push_source(node, Delta(ents))
                    any_data = True
            any_data = any_data or payload["any"]
            all_closed = all_closed and payload["closed"]
        return any_data, all_closed

    def _record_tick(self, rec, tick: int, woken_by: str, any_data: bool,
                     t_wake: float, t_drain: float, t_host: float,
                     t_end: float) -> None:
        """The spans of one commit tick, sharing ``("tick", tick)`` with
        the leg the tick submitted and, through ``RequestSpan.tick``, with
        the requests it picked up: ``tick`` from the loop's wake-up to
        ``run_time``'s return (every tick; ``woken_by`` says what ended
        the loop's wait, ``"period"`` or ``"request"``, and ``bound`` the
        rows its ingest drain was held to, where a budget stood), and on a
        tick that carried rows ``tick.drain`` around the drain and the cluster exchange and
        ``tick.host`` around ``run_time``, which returns with the device
        leg submitted (``t_host == t_end``: the tick skipped it)."""
        cause = ("tick", tick)
        by_source, requests, bound = self._last_drain_counts
        rec.span("tick", t_wake, t_end, cause,
                 rows=sum(by_source.values()), requests=requests,
                 woken_by=woken_by,
                 **({} if bound is None else {"bound": bound}))
        if any_data:
            rec.span("tick.drain", t_drain, t_host, cause, **by_source)
            if t_end > t_host:
                rec.span("tick.host", t_host, t_end, cause)

    def run(self) -> None:
        _ACTIVE_RUNTIMES.add(self)
        time_counter = 1
        restored_tick = 0
        replay_only = (
            self.persistence is not None
            and not getattr(self.persistence.config, "continue_after_replay",
                            True))
        reader_here = self.cluster is None or self.cluster.process_id == 0
        if self.persistence is not None:
            time_counter = self.persistence.restore_time() + 1
            if self.cluster is None:
                # bounded-time recovery: load the newest valid snapshot,
                # restore operator state at its tick and re-emit the
                # covered prefix's consolidated outputs — the WAL suffix
                # (replayed below via attach_source) is all that re-runs
                restored_tick = self._restore_snapshot()
            elif self.persistence.load_snapshot() is not None:
                # a snapshot-compacted root cannot restore under a
                # cluster (state is per-process; attach_source would
                # silently skip the covered records): fail loudly rather
                # than drop the covered prefix
                raise ValueError(
                    "persistence root carries an operator-state snapshot "
                    "but this run is clustered (PATHWAY_PROCESSES > 1) — "
                    "snapshot restore is single-process only. Re-run "
                    "single-process, or set PATHWAY_SNAPSHOT_RESTORE=0 "
                    "(sound only if the WAL was never compacted).")
        if self.replica is not None:
            # hydrate: newest valid snapshot generation -> operator state
            # (KNN re-upload, consolidated sink re-emission); the WAL
            # suffix replays through the first pump rounds below
            restored_tick = self.replica.hydrate(self.scheduler)
            # local ticks start past every tick the primary's root
            # already covers: one monotone clock across restore + tailing
            time_counter = max(restored_tick,
                               self.replica.driver.restore_time()) + 1
        for i, (node, session, datasource) in enumerate(self.sessions):
            live_session = session
            if self.replica is not None and self.replica.is_tailed(i):
                # tailed feed: rows arrive from the primary's WAL — the
                # reader thread must never start (it would double-ingest,
                # and the replica may not even reach the raw inputs).
                # Remembered: a promotion starts exactly these readers.
                self._tailed_sources.append(i)
                continue
            if self.persistence is not None and reader_here:
                # replay the durable prefix into `session`, then hand the
                # reader a recording proxy that skips the replayed count
                live_session = self.persistence.attach_source(datasource, session)
                self._drain_proxies[i] = live_session
            if replay_only or not reader_here:
                # pure replay (CLI `replay` without --continue) or a
                # non-reading cluster process: no live reader threads —
                # process 0 forwards this process's shard every tick
                session.close()
            else:
                self.supervisor.add_source(node, datasource, session,
                                           live_session)
        self.supervisor.start_all()
        if self.http_server is not None:
            self.http_server.start()
        if self.profiler is not None:
            self.profiler.start()

        # feed static tables at startup: dimension data (markdown tables,
        # static csv) joined against live streams must be present from tick
        # one. One tick per distinct logical time, like run_batch — a
        # single collapsed batch would net out add/retract pairs that
        # legitimately exist at different times (update streams). Static
        # feeds are SPMD-identical, so no cluster forwarding is needed.
        # Restored-snapshot runs SKIP them: the restored operator state
        # already includes the static rows (re-pushing would double-count
        # them; same assumption as replay — static inputs are unchanged
        # between runs).
        static_by_time, static_times = self.runner.static_feeds_by_time()
        if restored_tick:
            static_times = []
        for t in sorted(static_times):
            any_batch = False
            for node, groups in static_by_time:
                batch = groups.get(t)
                if batch:
                    self.scheduler.push_source(node, Delta(batch))
                    any_batch = True
            if any_batch:
                self.scheduler.run_time(time_counter)
                time_counter += 1

        commit_s = min(
            [s[2].autocommit_duration_ms or self.default_commit_ms
             for s in self.sessions] + [self.default_commit_ms]
        ) / 1000.0
        if self.replica is not None:
            # the loop cadence is also the WAL poll cadence — staleness
            # is bounded by max(commit interval, PATHWAY_REPLICA_POLL_MS)
            from pathway_tpu.engine.replica import _poll_interval_s

            commit_s = min(commit_s, _poll_interval_s())
        if self.qos is not None:
            # the tick interval IS the device-time budget denominator:
            # a fixed PATHWAY_QOS_QUERY_BUDGET partitions this many ms
            self.qos.tick_interval_ms = max(1.0, commit_s * 1e3)
        if self._control_client is not None:
            self._control_client.start()

        from pathway_tpu.engine.supervisor import Watchdog

        self.watchdog = Watchdog(self, self.supervisor, self.watchdog_config)
        self.watchdog.start()
        # teardown may write a FINAL operator-state snapshot, but only
        # after a clean loop exit: a loop dying mid-commit may have
        # consumed sealed entries (take_sealed) whose append never became
        # durable — a snapshot covering that state would mark them
        # processed while the restart's reader re-emits them (double
        # count). The flag flips only when the while-loop exits normally.
        loop_clean = False
        try:
            # Event wait, not time.sleep: a stop request ends the wait at
            # once, and so does a request pushed into a serving source
            # (_wait_for_tick). The commit interval is the longest a
            # request, or a row, waits for a tick; a row of an ingest
            # source waits for the period's tick, whatever woke the ticks
            # between (the PWT206 sleep-polling pattern this checker
            # family bans)
            rec = self.recorder
            period_due = _time.monotonic() + commit_s
            while True:
                woken_by = self._wait_for_tick(period_due)
                if woken_by == "stop":
                    break
                by_request = woken_by == "request"
                # the tick's spans (engine/flight_recorder.py): four clock
                # reads and up to three tuples a tick while recording
                t_wake = (_time.perf_counter()
                          if rec is not None and rec.enabled else None)
                self.last_tick_at = _time.monotonic()
                if self._promote_event.is_set():
                    # router-requested failover: runs HERE, synchronously
                    # between ticks, so it can never race a pump or drain
                    time_counter = self._execute_promotion(time_counter)
                # supervision tick: observe crashed/stalled readers, fire
                # scheduled backoff restarts, escalate exhausted retries
                if self.supervisor.poll() is not None:
                    if self.cluster is None:
                        break
                    # under a cluster, breaking out here would strand the
                    # peers mid-exchange (they block in Cluster.exchange
                    # until the recv timeout, then misreport a hung peer).
                    # Instead stop the local readers, close every local
                    # session with the error, and fall through: the normal
                    # tick merge sees all_closed on every process and the
                    # whole cluster leaves through the same lockstep
                    # end-of-stream path; the fatal re-raise below still
                    # fires on this process after teardown.
                    self.supervisor.request_stop()
                    for _node, session, _ds in self.sessions:
                        session.stopping.set()
                        session.close(reason="error",
                                      error=self.supervisor.fatal_error)
                # durability seals ride the drain itself: _drain_and_forward
                # drains each persisted source through its recording proxy's
                # seal_drain(tick), so "sealed at t" == "drained at t" ==
                # "complete once the tick-t leg resolves" holds EXACTLY —
                # required by operator-state snapshots (a seal taken before
                # the drain would let gap entries be processed at t but
                # recorded at t+1, double-counting them after a restore)
                if self.replica is not None and not by_request:
                    # tail the primary's WAL: every complete new primary
                    # commit tick is applied, coalesced per round into
                    # one local scheduler tick (engine/replica.py pump —
                    # advances applied_tick). On the period's ticks alone:
                    # the tailer counts quiet POLLS before it trusts the
                    # newest tick, and polls that follow requests would
                    # shorten that hold-back
                    time_counter = self.replica.pump(self, time_counter)
                if t_wake is not None:
                    t_drain = _time.perf_counter()
                any_data, all_closed, pushes = self._drain_and_forward(
                    time_counter, serving_only=by_request)
                if by_request and not any_data:
                    # the request that set the wake-up rode the tick
                    # before (it was pushed between that tick's wake-up
                    # and its drain): no tick to run, no span, no count
                    continue
                self.ticks_woken_by[woken_by] += 1
                any_data, all_closed = self._tick_sync(
                    time_counter, any_data, all_closed, pushes)
                if t_wake is not None:
                    t_host = t_end = _time.perf_counter()
                # under a cluster an idle tick would still pay one TCP
                # round per exchanged node inside run_time; the merged
                # any_data is identical on every process, so skipping is
                # SPMD-consistent (single-process keeps ticking — empty
                # ticks are near-free and drive as-of-now retractions)
                if self.cluster is None or any_data:
                    t_tick0 = _time.perf_counter()
                    self.scheduler.run_time(time_counter)
                    if t_wake is not None:
                        t_end = _time.perf_counter()
                    # stamp after the step too: a long (healthy) batch
                    # counts as progress the moment it completes, so only
                    # a single step exceeding the deadline can ever be
                    # reported as a stall. Under pipelined execution
                    # run_time returns with device legs still in flight —
                    # that IS progress (backpressure, not the watchdog,
                    # bounds a slow device; every resolved leg also
                    # stamps progress via the watermark listener).
                    self.last_tick_at = _time.monotonic()
                    self._last_completed_tick = time_counter
                    self._tick_feedback(
                        time_counter,
                        (_time.perf_counter() - t_tick0) * 1e3, commit_s,
                        by_request)
                    # close every live semantic result cache's
                    # invalidations/tick window (engine/result_cache.py)
                    # — the basis of the exported invalidations-per-tick
                    # rate and the bench leg's staleness accounting
                    from pathway_tpu.engine.result_cache import \
                        note_commit_ticks

                    note_commit_ticks()
                    self.monitor.update(self.scheduler, self.runner.graph,
                                        time_counter)
                    if self.persistence is not None:
                        # resolved-prefix commit watermark: checkpoint
                        # the longest prefix of ticks whose device legs
                        # have retired instead of draining the bridge —
                        # a record can still never cover a tick that
                        # could fail, but checkpoint cadence no longer
                        # prices pipelining at effective depth 1
                        self._commit_watermark_tick(time_counter)
                        if not by_request \
                                and self._snapshot_due(time_counter):
                            # bounded-time recovery: operator-state
                            # snapshot anchored to the watermark + WAL
                            # compaction (engine/persistence.py)
                            self._snapshot_pass(time_counter)
                if t_wake is not None:
                    self._record_tick(rec, time_counter, woken_by, any_data,
                                      t_wake, t_drain, t_host, t_end)
                time_counter += 1
                if not by_request:
                    period_due = _time.monotonic() + commit_s
                if all_closed and not any_data:
                    # re-drain: a source may have pushed between its drain()
                    # and closing — loop until truly empty, then final tick
                    leftovers = True
                    while leftovers:
                        # unbudgeted: every source closed — deferred
                        # ingest drains to completion at full throughput
                        any_data, _closed, pushes = self._drain_and_forward(
                            time_counter, budgeted=False)
                        any_data, _closed = self._tick_sync(
                            time_counter, any_data, True, pushes)
                        leftovers = any_data
                        if leftovers:
                            self.scheduler.run_time(time_counter)
                            time_counter += 1
                    # all sources closed: end-of-stream flush tick (a hard
                    # resolve barrier under pipelined execution)
                    self.scheduler.run_time(time_counter, flush=True)
                    self._last_completed_tick = time_counter
                    if self.persistence is not None:
                        # end-of-stream keeps its hard barrier (the flush
                        # tick above) — this full commit seals and
                        # persists everything, watermark == final tick
                        self.persistence.commit(time_counter)
                        self._flush_durable_acks(time_counter)
                    break
            loop_clean = True
        except BaseException as e:  # noqa: BLE001 — escalation decides
            # poisoned device leg / exhausted persistence retries /
            # operator failure: the finally below first commits the last
            # fully-resolved prefix, then either degrade
            # (terminate_on_error=False: absorbed, recorded) or terminate
            # (re-raise to pw.run's caller after a clean teardown)
            if not self._handle_engine_failure(e):
                raise
        finally:
            # teardown: stop reader threads FIRST so nothing pushes into a
            # closed pipeline, then join them (a reader that ignores the
            # stop event is a bug the thread-leak test fixture catches)
            self._stop.set()  # natural loop exits must also stop helpers
            if self.qos is not None:
                # release the module-global hook: a later QoS-off run in
                # this process must not credit a dead run's controller
                from pathway_tpu.engine.qos import (current_controller,
                                                    install_controller)

                if current_controller() is self.qos:
                    install_controller(None)
            if self._control_client is not None:
                self._control_client.stop()
            self.watchdog.stop()
            self.supervisor.request_stop()
            for _node, session, _ds in self.sessions:
                session.stopping.set()
            self.join_readers()
            _ACTIVE_RUNTIMES.discard(self)
            if self.recorder is not None:
                # written in the finally so a crashed run still leaves its
                # trace on disk (the post-mortem artifact)
                try:
                    self.recorder.write_chrome_trace()
                except Exception:
                    import logging

                    logging.getLogger(__name__).warning(
                        "failed to write trace to %s",
                        self.recorder.trace_path, exc_info=True)
            self.monitor.close()
            self.scheduler.close()
            if self.persistence is not None:
                # final resolved-prefix commit: scheduler.close() drained
                # the bridge, so the watermark now covers every leg that
                # retired (a poisoned bridge froze it at the last clean
                # tick) — stop/crash paths keep exactly the resolved
                # prefix durable, never a tick that could still fail
                try:
                    self._commit_watermark_tick(self._last_completed_tick)
                except Exception:
                    import logging

                    logging.getLogger(__name__).warning(
                        "final watermark commit failed during teardown; "
                        "the previous commit's prefix stays durable",
                        exc_info=True)
                # final snapshot on CLEAN shutdown only, and only if the
                # watermark advanced since the last one (write_snapshot's
                # guard — no empty-generation churn). A poisoned bridge /
                # degraded run keeps operator state inconsistent with the
                # frozen watermark, so those paths stay WAL-only.
                if self._snapshots_enabled() and loop_clean \
                        and self.supervisor.fatal_error is None \
                        and self._degraded_engine_error is None \
                        and self.scheduler.take_device_error() is None:
                    try:
                        self._snapshot_pass(self._last_completed_tick)
                    except Exception:
                        import logging

                        logging.getLogger(__name__).warning(
                            "final snapshot failed during teardown; the "
                            "WAL alone stays authoritative",
                            exc_info=True)
                self.persistence.close()
            if self.replica is not None:
                self.replica.close()
            if self.profiler is not None:
                # stop the sampler + any in-flight capture; release the
                # module-global hook only if this run installed it (a
                # test-installed profiler outlives the run untouched)
                self.profiler.stop()
                if self._installed_profiler:
                    from pathway_tpu.engine.profiler import (
                        current_profiler, install_profiler)

                    if current_profiler() is self.profiler:
                        install_profiler(None)
            if self.http_server is not None:
                self.http_server.stop()
        fatal = self.supervisor.fatal_error
        if fatal is not None:
            # escalation under terminate_on_error=True: surface the
            # connector's own exception (its reader-thread traceback is
            # attached) from pw.run, after a full clean teardown
            raise fatal
        # a device leg that failed after the loop's last submit (e.g. the
        # run was stopped externally) was drained-but-not-raised by
        # scheduler.close(): surface it now, exactly as synchronous mode
        # would have raised it out of run_time — unless the degrade path
        # already absorbed and recorded this exact failure
        deferred = self.scheduler.take_device_error()
        if deferred is not None \
                and deferred is not self._degraded_engine_error:
            if self.terminate_on_error or not self._handle_engine_failure(
                    deferred):
                raise deferred
