"""pw.run — execute the collected pipeline
(reference: python/pathway/internals/run.py:12-52)."""

from __future__ import annotations

from typing import Any

from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.runner import GraphRunner


def run(*, debug: bool = False, monitoring_level=None, with_http_server: bool = False,
        default_logging: bool = True, persistence_config=None,
        runtime_typechecking: bool | None = None, terminate_on_error: bool = True,
        telemetry_config=None, static_check: str | None = None,
        connector_policy=None, watchdog=None, trace_path: str | None = None,
        replica_of: str | None = None, qos=None, **kwargs) -> Any:
    """Build the engine graph from all registered outputs and run it.

    Static-only graphs run in batch mode to completion; graphs with streaming
    sources enter the realtime microbatch loop (pathway_tpu/engine/streaming.py)
    until all sources finish or the process is stopped. The loop ticks at
    the smallest ``autocommit_duration_ms`` of its sources (100 ms at
    most): that period is the cadence of ingest and the longest a request
    of a ``rest_connector`` waits for a tick, since a waiting request
    wakes the loop before the period runs out.

    ``trace_path`` (or ``PATHWAY_TRACE_PATH``) turns on the flight
    recorder (engine/flight_recorder.py) and writes the run's span buffer
    as Chrome trace-event JSON — host and device legs on separate tracks,
    per-operator spans with user-frame attribution — loadable directly in
    Perfetto (README "Observability").

    ``connector_policy`` is the default :class:`pw.ConnectorPolicy`
    (retry/backoff/escalation) applied to streaming sources that did not
    pick their own; ``watchdog`` a :class:`pw.WatchdogConfig` tuning stall
    detection (engine/supervisor.py). With ``terminate_on_error=True`` a
    connector whose retries are exhausted stops the runtime and its
    exception re-raises from here; with ``False`` the failure lands in the
    global error log and the rest of the pipeline keeps serving.

    ``static_check`` runs the pre-execution analyzer
    (internals/static_check/) over the collected plan DAG first:
    ``"warn"`` logs every diagnostic, ``"error"`` additionally raises
    :class:`StaticCheckError` on error-severity findings, ``"off"`` (the
    default, also settable via ``PATHWAY_STATIC_CHECK``) skips analysis.
    ``PATHWAY_STATIC_CHECK_MESH`` (e.g. ``"4x2"``) arms the mesh-dependent
    sharding checks (PWT1xx) against that topology. The UDF-traceability
    classifications recorded on apply expressions (``_shard_class``) feed
    the auto-jit tier (internals/autojit.py): traceable/vmappable sync
    UDF chains compile into fused vectorized dispatches at graph lowering,
    byte-identical to the interpreted path, on by default and disabled
    with ``PATHWAY_AUTO_JIT=0`` (README "Auto-jit").

    ``qos`` (or ``PATHWAY_QOS=1``) arms the QoS control plane
    (engine/qos.py): per-tick device-time budgeting between query and
    ingest work steered by the SLO burn rate, bounded query admission
    with deadline-aware shedding (503 + ``Retry-After``), and
    cross-request coalescing accounting. ``True`` / a
    :class:`pw.QosConfig` enable it, ``False`` disables explicitly
    (the PWT013 waiver), ``None`` defers to the environment. QoS
    implies the flight recorder (the controller feeds on the request
    tracker; README "QoS & admission control").

    ``replica_of`` (or ``PATHWAY_REPLICA_OF``) runs this program as a
    snapshot-hydrated READ REPLICA of the primary whose persistence root
    it names (engine/replica.py): operator state restores from the newest
    valid snapshot generation, persisted feeds are tailed from the
    primary's WAL through a read-only driver, rest routes serve
    ``query_as_of_now`` at the replica's applied tick, and — when
    ``PATHWAY_ROUTER_CONTROL`` names a router (engine/router.py) — the
    process registers and heartbeats staleness/latency over the framed
    HMAC control channel (README "Replica fleet").
    """
    import os as _os

    from pathway_tpu.internals.config import get_pathway_config

    if replica_of is None:
        replica_of = _os.environ.get("PATHWAY_REPLICA_OF") or None
    if persistence_config is None and replica_of is None:
        persistence_config = _persistence_config_from_env()
    replica = None
    if replica_of is not None:
        from pathway_tpu.engine.replica import ReplicaTailer

        replica = ReplicaTailer(replica_of)
    _run_static_check(static_check, persistence_config, terminate_on_error,
                      connector_policy, qos=qos)

    cfg = get_pathway_config()
    cluster = None
    if cfg.processes > 1:
        # SPMD cluster: every process runs this same program and owns a
        # contiguous block of PATHWAY_THREADS logical workers; rows cross
        # processes at exchange boundaries over TCP (engine/multiproc.py;
        # reference: timely cluster, config.rs:62-120, cli spawn -n)
        from pathway_tpu.engine.multiproc import get_cluster

        cluster = get_cluster()
    from pathway_tpu.internals.telemetry import Config as TelemetryConfig
    from pathway_tpu.internals.telemetry import Telemetry

    if telemetry_config is None:
        telemetry_config = TelemetryConfig.create()
    telemetry = Telemetry(telemetry_config)

    runner = GraphRunner()
    with telemetry.span("pathway.graph.build"):
        for binder in G.output_binders:
            binder(runner)
    if persistence_config is not None:
        runner._persistence_config = persistence_config
    try:
        with telemetry.span("pathway.run",
                            run_id=telemetry_config.run_id or ""):
            if runner._stream_subjects:
                from pathway_tpu.engine.streaming import StreamingRuntime

                rt = StreamingRuntime(
                    runner, monitoring_level=monitoring_level,
                    with_http_server=with_http_server,
                    persistence_config=persistence_config,
                    terminate_on_error=terminate_on_error,
                    connector_policy=connector_policy, watchdog=watchdog,
                    cluster=cluster, trace_path=trace_path,
                    replica=replica, qos=qos)
                telemetry.register_scheduler_gauges(rt.scheduler,
                                                    runner.graph)
                if rt.recorder is not None:
                    # recorded spans also flow through the OTel provider
                    # when a real SDK pipeline is configured
                    rt.recorder.set_telemetry(telemetry)
                rt.run()
            else:
                if replica is not None:
                    raise ValueError(
                        "replica_of= requires a streaming pipeline (a "
                        "batch graph has no WAL to tail and nothing to "
                        "serve)")
                from pathway_tpu.engine.flight_recorder import FlightRecorder

                recorder = FlightRecorder.from_env(trace_path=trace_path)
                if recorder is not None:
                    recorder.set_telemetry(telemetry)
                runner.run_batch(cluster=cluster, recorder=recorder)
    finally:
        telemetry.shutdown()
    return runner


def run_all(**kwargs):
    return run(**kwargs)


def _run_static_check(mode: str | None, persistence_config,
                      terminate_on_error: bool | None = None,
                      connector_policy=None, qos=None) -> None:
    """Opt-in pre-execution analysis gate for pw.run."""
    import os

    if mode is None:
        mode = os.environ.get("PATHWAY_STATIC_CHECK", "off")
    if mode in ("off", "", None):
        return
    if mode not in ("warn", "error"):
        raise ValueError(
            f"static_check must be 'off', 'warn' or 'error', got {mode!r}")
    import logging

    from pathway_tpu.internals.static_check import (Severity, StaticCheckError,
                                                    analyze)

    # PWT013 arming (the run knows its own QoS decision — the analyzer's
    # tri-state: True/False are decisions, None defers to the env)
    qos_enabled: bool | None
    if qos is None:
        from pathway_tpu.engine.qos import qos_enabled_from_env

        qos_enabled = qos_enabled_from_env()
    else:
        qos_enabled = bool(qos)
    diagnostics = analyze(
        graph=G, persisted=persistence_config is not None,
        mesh=os.environ.get("PATHWAY_STATIC_CHECK_MESH") or None,
        terminate_on_error=terminate_on_error,
        connector_policy=connector_policy, qos_enabled=qos_enabled)
    if not diagnostics:
        return
    log = logging.getLogger("pathway_tpu.static_check")
    levels = {Severity.ERROR: logging.ERROR,
              Severity.WARNING: logging.WARNING,
              Severity.INFO: logging.INFO}
    # errors first, and each finding at its own severity so log-level
    # filters and warning-based alerting see what the analyzer meant
    for d in sorted(diagnostics, key=lambda d: levels[d.severity],
                    reverse=True):
        log.log(levels[d.severity], "%s", d)
    if mode == "error" and any(d.is_error for d in diagnostics):
        raise StaticCheckError(diagnostics)


def _persistence_config_from_env():
    """Record/replay wiring set by the CLI (cli.py spawn --record / replay):
    PATHWAY_REPLAY_STORAGE + PATHWAY_SNAPSHOT_ACCESS + PATHWAY_PERSISTENCE_MODE
    + PATHWAY_CONTINUE_AFTER_REPLAY (reference: cli.py:178-187, engine env)."""
    import os

    path = os.environ.get("PATHWAY_REPLAY_STORAGE") or os.environ.get(
        "PATHWAY_PERSISTENT_STORAGE")
    if not path:
        return None
    from pathway_tpu import persistence

    mode = os.environ.get("PATHWAY_PERSISTENCE_MODE", "persisting")
    cont = os.environ.get("PATHWAY_CONTINUE_AFTER_REPLAY", "")
    access = os.environ.get("PATHWAY_SNAPSHOT_ACCESS", "")
    continue_after_replay = cont.lower() in ("1", "true", "yes") or (
        access == "record")
    return persistence.Config(
        backend=persistence.Backend.filesystem(path),
        persistence_mode=mode,
        continue_after_replay=continue_after_replay,
    )
