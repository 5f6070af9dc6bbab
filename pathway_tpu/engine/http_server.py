"""Per-process HTTP monitoring endpoint.

Rebuild of the reference's hyper-based server (src/engine/http_server.rs:77
``start_http_server_thread`` + ``metrics_from_stats`` :25): serves
``/status`` (JSON snapshot of runtime progress) and ``/metrics``
(Prometheus/OpenMetrics text) on ``PATHWAY_MONITORING_HTTP_PORT +
process_id`` (default base 20000, like the reference).
"""

from __future__ import annotations

import json
import os
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer


def monitoring_port() -> int:
    base = int(os.environ.get("PATHWAY_MONITORING_HTTP_PORT", "20000"))
    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    return base + pid


def _paged_stats() -> dict | None:
    """Aggregate paged-store occupancy (engine/paged_store.py), or None
    when no paged pool is live in this process."""
    try:
        from pathway_tpu.engine.paged_store import live_paged_stats

        return live_paged_stats()
    except Exception:
        return None


def _expert_load() -> dict | None:
    """Tokens per held expert of the live embedders whose model routes to
    experts (xpacks/llm/embedders.py), or None. The one place the sum
    leaves the device: a metrics request, never a tick."""
    try:
        from pathway_tpu.xpacks.llm.embedders import expert_load_stats

        return expert_load_stats()
    except Exception:
        return None


def _scan_lowerings() -> dict | None:
    """Delta-rule scans lowered in this process by the lowering they took
    (ops/deltanet.py), or None where no program holds one (a process
    that never loaded the module does not load it for this)."""
    module = sys.modules.get("pathway_tpu.ops.deltanet")
    took = module.scan_lowerings() if module is not None else {}
    return took if any(took.values()) else None


def _attention_stats() -> tuple[dict | None, dict | None]:
    """(attention cores lowered in this process by the lowering they took,
    key blocks the live embedders' attention ran of all up to the
    diagonal): ops/attention.py, xpacks/llm/embedders.py; each None where
    there is nothing (a process that never loaded a module does not load
    it for this)."""
    module = sys.modules.get("pathway_tpu.ops.attention")
    took = module.attention_lowerings() if module is not None else {}
    embedders = sys.modules.get("pathway_tpu.xpacks.llm.embedders")
    tiles = embedders.attention_tile_stats() if embedders is not None \
        else None
    return (took if any(took.values()) else None), tiles


def _cache_stats() -> dict | None:
    """Aggregate semantic-result-cache stats (engine/result_cache.py),
    or None when no cache is live in this process."""
    try:
        from pathway_tpu.engine.result_cache import live_cache_stats

        return live_cache_stats()
    except Exception:
        return None


def _profiler_stats() -> dict | None:
    """Continuous-profiler snapshot (engine/profiler.py), or None when
    no profiler is installed in this process."""
    try:
        from pathway_tpu.engine.profiler import live_profiler_stats

        return live_profiler_stats()
    except Exception:
        return None


class MonitoringHttpServer:
    def __init__(self, runtime, port: int | None = None):
        self.runtime = runtime
        self.port = port if port is not None else monitoring_port()
        self._httpd: ThreadingHTTPServer | None = None
        self._thread: threading.Thread | None = None

    # -- payloads ----------------------------------------------------------
    def status_payload(self) -> dict:
        sched = self.runtime.scheduler
        graph = self.runtime.runner.graph
        operators = []
        for node in graph.nodes:
            st = sched.stats.get(node.id, {})
            operators.append({
                "id": node.id,
                "name": node.name or type(node.op).__name__,
                "insertions": st.get("insertions", 0),
                "retractions": st.get("retractions", 0),
                "latency_ms": round(st.get("latency_ms", 0.0), 3),
                "total_ms": round(st.get("total_ms", 0.0), 3),
            })
        payload = {
            "process_id": int(os.environ.get("PATHWAY_PROCESS_ID", "0")),
            # serving role in the replica fleet (engine/replica.py /
            # engine/router.py): "primary" (owns writes + the WAL) or
            # "replica" (snapshot-hydrated, tails the WAL read-only);
            # the router process reports "router" from its own endpoint
            "role": getattr(self.runtime, "role", "primary"),
            "sources": len(self.runtime.sessions),
            "operators": operators,
        }
        replica = getattr(self.runtime, "replica", None)
        if replica is not None:
            # hydration + staleness snapshot: how far this replica's
            # applied tick trails the primary's durable watermark
            payload["replica"] = replica.stats()
            payload["applied_tick"] = replica.applied_tick
            payload["staleness_ticks"] = replica.staleness_ticks()
        if getattr(self.runtime, "promotions", 0):
            # write-path failover: this process started as a replica and
            # was promoted to primary (role above already says "primary")
            payload["promotions"] = self.runtime.promotions
            payload["promotion_tick"] = self.runtime.promotion_tick
            fp = getattr(self.runtime, "failover_promotion_s", None)
            if fp is not None:
                payload["failover_promotion_s"] = round(fp, 6)
        # critical-path attribution: which operator dominated the last
        # tick. latency_ms is each operator's LAST step latency, so the
        # max over operators is exactly the last tick's dominator; the
        # flight recorder (when on) adds leg + user-frame detail.
        if operators:
            dom = max(operators, key=lambda o: o["latency_ms"])
            payload["last_tick_dominator"] = {
                "operator": dom["name"], "ms": dom["latency_ms"]}
            rec = getattr(sched, "recorder", None)
            if rec is not None and rec.enabled:
                detail = rec.dominator()
                if detail is not None:
                    payload["last_tick_dominator"] = detail
        bridge = sched.bridge_stats() if hasattr(sched, "bridge_stats") \
            else None
        if bridge is not None:
            bridge = dict(bridge)
            bridge["inflight"] = sched._bridge.inflight() \
                if getattr(sched, "_bridge", None) is not None else None
            payload["device_bridge"] = bridge
        tracker = self._request_tracker()
        if tracker is not None:
            # serving-path SLO snapshot (engine/request_tracker.py):
            # request counts, e2e quantiles, per-stage p50s, burn rate —
            # and the tail of over-budget requests with their dominant
            # stage (README "Serving SLO")
            payload["serving"] = tracker.summary()
            payload["slow_queries"] = tracker.slow_queries()
        qos = getattr(self.runtime, "qos", None)
        if qos is not None:
            # QoS control plane (engine/qos.py): budget partition,
            # admission queue, shed/deferral/coalescing counters —
            # the closed loop's own state next to the measurements
            payload["qos"] = qos.summary()
        try:
            # auto-jit tier state (internals/autojit.py): enabled flag,
            # fused-program count, backend mix (xla/numpy/interp after
            # demotions), compile/dispatch/demotion counters
            from pathway_tpu.internals.autojit import autojit_stats

            payload["autojit"] = autojit_stats()
        except Exception:
            pass
        paged = _paged_stats()
        if paged is not None:
            # paged vector store (engine/paged_store.py): page table
            # occupancy, extent count, growth events, per-tenant pages
            payload["paged_store"] = paged
        rc = _cache_stats()
        if rc is not None:
            # semantic result cache (engine/result_cache.py): hit/miss/
            # invalidation counters, entry count, the index-version
            # watermark riding the heartbeats, invalidations per tick
            payload["result_cache"] = rc
        prof = _profiler_stats()
        if prof is not None:
            # continuous profiling plane (engine/profiler.py): host
            # sampler state + per-kernel-family cost-model aggregates
            # with the roofline classification (arithmetic intensity vs
            # machine balance, compute- vs bandwidth-bound)
            payload["profiler"] = prof
        persistence = getattr(self.runtime, "persistence", None)
        if persistence is not None:
            # commit-watermark durability (engine/persistence.py): how
            # far checkpoints trail the pipeline — a growing lag is
            # visible here before it ever becomes a stall
            payload["persistence"] = persistence.stats()
        return payload

    def _request_tracker(self):
        rec = getattr(self.runtime.scheduler, "recorder", None)
        if rec is not None and rec.enabled:
            return rec.requests
        return None

    def trace_payload(self) -> dict:
        """``/trace``: the flight recorder's last-N-ticks span buffer
        (empty shell with enabled=false when nothing is recording)."""
        rec = getattr(self.runtime.scheduler, "recorder", None)
        if rec is None:
            return {"enabled": False, "events": [], "device_legs": [],
                    "inflight": None}
        return rec.trace_payload()

    def chrome_trace_payload(self) -> dict:
        """``/trace?format=chrome``: the same buffer as Chrome trace-event
        JSON with the ``pathway_meta`` fleet block — what the router's
        ``/fleet/trace`` and ``python -m pathway_tpu trace-merge`` consume
        (engine/fleet_observability.py). Without a recorder the shell
        still carries this process's identity so a merge over a partially
        instrumented fleet stays well-formed."""
        import os as _os

        rec = getattr(self.runtime.scheduler, "recorder", None)
        if rec is None:
            return {"traceEvents": [], "displayTimeUnit": "ms",
                    "pathway_meta": {
                        "pid": _os.getpid(),
                        "process": _os.environ.get("PATHWAY_REPLICA_ID")
                        or f"pid{_os.getpid()}",
                        "role": getattr(self.runtime, "role", "primary"),
                        "epoch_wall_us": 0.0}}
        return rec.chrome_trace_payload()

    def healthz_payload(self) -> tuple[bool, dict]:
        """(healthy, body) for ``/healthz``: 200 while every supervised
        source is live and the commit loop ticks; 503 with a body naming
        failed/stalled sources and retry counts once degraded (contract in
        README "Fault tolerance")."""
        from pathway_tpu.engine.threads import crashed_threads

        sup = getattr(self.runtime, "supervisor", None)
        failed: list[dict] = []
        stalled: list[str] = []
        retries: dict[str, int] = {}
        commit_stalled = False
        crashes = crashed_threads()
        # with a supervisor, its predicate owns the health definition
        # (it already folds in crashed threads scoped to its run);
        # without one (standalone monitoring), a crashed engine thread
        # must still flip the status — body and code may never disagree
        healthy = not crashes
        if sup is not None:
            healthy = sup.healthy()
            commit_stalled = sup.commit_stalled
            for s in sup.summary():
                retries[s["source"]] = s["restarts"]
                if s["state"] == "failed":
                    failed.append({"source": s["source"],
                                   "error": s["error"],
                                   "restarts": s["restarts"]})
                if s["stalled"]:
                    stalled.append(s["source"])
        replica = getattr(self.runtime, "replica", None)
        return healthy, {
            "status": "healthy" if healthy else "degraded",
            "role": getattr(self.runtime, "role", "primary"),
            "applied_tick": (replica.applied_tick if replica is not None
                             else (self.runtime.persistence
                                   .last_commit_watermark
                                   if getattr(self.runtime, "persistence",
                                              None) is not None else
                                   getattr(self.runtime,
                                           "_last_completed_tick", 0))),
            "staleness_ticks": (replica.staleness_ticks()
                                if replica is not None else 0),
            "failed_sources": failed,
            "stalled_sources": stalled,
            "commit_loop_stalled": commit_stalled,
            "engine_failed": bool(sup is not None
                                  and getattr(sup, "engine_failed", False)),
            # engine threads dead of an uncaught exception (excepthook in
            # engine/threads.py) — non-empty degrades the run
            "crashed_threads": crashes,
            "connector_retries": retries,
        }

    def metrics_payload(self) -> str:
        # OpenMetrics text format, one family per counter kind
        # (reference exposes input/output latency gauges + process metrics).
        lines = [
            "# TYPE pathway_tpu_insertions counter",
            "# TYPE pathway_tpu_retractions counter",
            "# TYPE pathway_tpu_operator_latency_ms gauge",
            "# TYPE pathway_tpu_operator_total_ms counter",
        ]
        # the one exposition-escaping contract, shared with the router
        # and the fleet merger (engine/fleet_observability.py)
        from pathway_tpu.engine.fleet_observability import \
            escape_label_value as esc

        payload = self.status_payload()
        for op in payload["operators"]:
            labels = f'{{operator="{esc(op["name"])}",id="{op["id"]}"}}'
            lines.append(f"pathway_tpu_insertions{labels} {op['insertions']}")
            lines.append(f"pathway_tpu_retractions{labels} {op['retractions']}")
            lines.append(
                f"pathway_tpu_operator_latency_ms{labels} {op['latency_ms']}")
            lines.append(
                f"pathway_tpu_operator_total_ms{labels} {op['total_ms']}")
        rec = getattr(self.runtime.scheduler, "recorder", None)
        if rec is not None and rec.enabled:
            ops = rec.op_stats()
            if ops:
                # per-operator step-latency histograms + row counters from
                # the flight recorder (engine/flight_recorder.py) — the
                # stage-level visibility the reference exports as OTLP
                # latency gauges (telemetry.rs:312-366)
                lines.append("# TYPE pathway_tpu_operator_step_duration_ms"
                             " histogram")
                lines.append("# TYPE pathway_tpu_operator_rows_in counter")
                lines.append("# TYPE pathway_tpu_operator_rows_out counter")
                lines.append("# TYPE pathway_tpu_operator_reducer_rederived"
                             " counter")
                for st in ops:
                    base = f'operator="{esc(st["name"])}",id="{st["id"]}"'
                    for le, c in st["buckets"]:
                        le_s = "+Inf" if le == float("inf") \
                            else format(le, "g")
                        lines.append(
                            "pathway_tpu_operator_step_duration_ms_bucket"
                            f'{{{base},le="{le_s}"}} {c}')
                    lines.append(
                        "pathway_tpu_operator_step_duration_ms_sum"
                        f"{{{base}}} {round(st['sum_ms'], 6)}")
                    lines.append(
                        "pathway_tpu_operator_step_duration_ms_count"
                        f"{{{base}}} {st['count']}")
                    lines.append(
                        f"pathway_tpu_operator_rows_in{{{base}}} "
                        f"{st['rows_in']}")
                    lines.append(
                        f"pathway_tpu_operator_rows_out{{{base}}} "
                        f"{st['rows_out']}")
                    lines.append(
                        f"pathway_tpu_operator_reducer_rederived{{{base}}} "
                        f"{st['rederived']}")
        tracker = self._request_tracker()
        if tracker is not None and tracker.count:
            # serving-path SLO families (engine/request_tracker.py):
            # streaming e2e quantiles as a Prometheus summary, per-stage
            # p50/sum/count, and the burn-rate gauge the PR-7 scheduler
            # will consume
            qs = tracker.quantiles_ms()
            lines.append(
                "# TYPE pathway_tpu_query_e2e_latency_ms summary")
            if qs is not None:
                for q, v in qs.items():
                    lines.append(
                        "pathway_tpu_query_e2e_latency_ms"
                        f'{{quantile="{format(q, "g")}"}} {round(v, 6)}')
            lines.append("pathway_tpu_query_e2e_latency_ms_sum "
                         f"{round(tracker.sum_ms, 6)}")
            lines.append("pathway_tpu_query_e2e_latency_ms_count "
                         f"{tracker.count}")
            lines.append("# TYPE pathway_tpu_query_stage_ms summary")
            for stage, agg in tracker.stage_summary().items():
                if agg["p50_ms"] is not None:
                    lines.append(
                        "pathway_tpu_query_stage_ms"
                        f'{{stage="{esc(stage)}",quantile="0.5"}} '
                        f"{round(agg['p50_ms'], 6)}")
                lines.append(
                    f'pathway_tpu_query_stage_ms_sum{{stage="{esc(stage)}"}}'
                    f" {agg['sum_ms']}")
                lines.append(
                    "pathway_tpu_query_stage_ms_count"
                    f'{{stage="{esc(stage)}"}} {tracker.count}')
            lines.append("# TYPE pathway_tpu_query_slo_violations counter")
            lines.append(
                f"pathway_tpu_query_slo_violations {tracker.violations}")
            lines.append("# TYPE pathway_tpu_slo_target_ms gauge")
            lines.append(f"pathway_tpu_slo_target_ms {tracker.slo_ms}")
            lines.append("# TYPE pathway_tpu_slo_burn_rate gauge")
            lines.append(
                f"pathway_tpu_slo_burn_rate {round(tracker.burn_rate(), 6)}")
            tenants = tracker.tenant_summary()
            if tenants:
                # per-tenant serving SLOs (the multi-tenant isolation
                # surface): e2e quantiles under the SAME summary family
                # as above, split by the tenant the searched index
                # belongs to, plus each tenant's own burn rate
                for tenant, ts in sorted(tenants.items()):
                    tlab = f'tenant="{esc(tenant)}"'
                    for q, v in (("0.5", ts["p50_ms"]),
                                 ("0.95", ts["p95_ms"])):
                        if v is not None:
                            lines.append(
                                "pathway_tpu_query_e2e_latency_ms"
                                f'{{{tlab},quantile="{q}"}} {v}')
                    lines.append(
                        "pathway_tpu_query_e2e_latency_ms_count"
                        f"{{{tlab}}} {ts['count']}")
                lines.append(
                    "# TYPE pathway_tpu_tenant_slo_burn_rate gauge")
                for tenant, ts in sorted(tenants.items()):
                    lines.append(
                        "pathway_tpu_tenant_slo_burn_rate"
                        f'{{tenant="{esc(tenant)}"}} {ts["burn_rate"]}')
        qos = getattr(self.runtime, "qos", None)
        if qos is not None:
            # QoS control plane (engine/qos.py): the budget the
            # controller currently reserves for query work, the
            # admission queue level, and the shed / deferral /
            # coalescing counters — every shed query is accounted here
            # (and got its 503 + Retry-After), nothing sheds silently
            qsum = qos.summary()
            lines.append("# TYPE pathway_tpu_qos_query_budget_ms gauge")
            lines.append(f"pathway_tpu_qos_query_budget_ms "
                         f"{qsum['query_budget_ms']}")
            lines.append(
                "# TYPE pathway_tpu_qos_admission_queue_depth gauge")
            lines.append(f"pathway_tpu_qos_admission_queue_depth "
                         f"{qsum['admission_queue_depth']}")
            lines.append("# TYPE pathway_tpu_qos_shed_total counter")
            lines.append(
                f"pathway_tpu_qos_shed_total {qsum['shed_total']}")
            lines.append("# TYPE pathway_tpu_qos_ingest_deferrals counter")
            lines.append(f"pathway_tpu_qos_ingest_deferrals "
                         f"{qsum['ingest_deferrals']}")
            lines.append(
                "# TYPE pathway_tpu_qos_coalesced_queries counter")
            lines.append(f"pathway_tpu_qos_coalesced_queries "
                         f"{qsum['coalesced_queries']}")
            lines.append(
                "# TYPE pathway_tpu_qos_coalesced_dispatches counter")
            lines.append(f"pathway_tpu_qos_coalesced_dispatches "
                         f"{qsum['coalesced_dispatches']}")
            lines.append("# TYPE pathway_tpu_qos_shedding gauge")
            lines.append(f"pathway_tpu_qos_shedding "
                         f"{1 if qsum['shedding'] else 0}")
        cluster = getattr(self.runtime, "cluster", None)
        if cluster is not None and getattr(cluster, "stats", None):
            # exchange-plane cost per row (engine/multiproc.py), split by
            # transport (tcp sockets vs same-host shared-memory rings):
            # the surface that makes an encdec regression visible per-run
            # AND shows which link kind carried the rows
            cst = cluster.stats
            by_t = getattr(cluster, "stats_by_transport", None) or {}
            lines.append(
                "# TYPE pathway_tpu_exchange_encode_us_per_row gauge")
            for t in sorted(by_t):
                lines.append(
                    f'pathway_tpu_exchange_encode_us_per_row'
                    f'{{transport="{esc(t)}"}} '
                    f"{round(cluster.encode_us_per_row(t), 6)}")
            lines.append(
                "# TYPE pathway_tpu_exchange_decode_us_per_row gauge")
            for t in sorted(by_t):
                lines.append(
                    f'pathway_tpu_exchange_decode_us_per_row'
                    f'{{transport="{esc(t)}"}} '
                    f"{round(cluster.decode_us_per_row(t), 6)}")
            for fam in ("rows_out", "rows_in", "bytes_out", "bytes_in",
                        "messages"):
                lines.append(f"# TYPE pathway_tpu_exchange_{fam} counter")
                for t in sorted(by_t):
                    lines.append(
                        f'pathway_tpu_exchange_{fam}'
                        f'{{transport="{esc(t)}"}} {by_t[t][fam]}')
            # slab traffic that bypassed the sockets entirely (bytes_out
            # above counts doorbells only for shm links) and the global
            # barrier count, which spans transports
            lines.append("# TYPE pathway_tpu_exchange_shm_bytes counter")
            shm_total = (cst.get("shm_bytes_out", 0)
                         + cst.get("shm_bytes_in", 0))
            lines.append(f"pathway_tpu_exchange_shm_bytes {shm_total}")
            lines.append("# TYPE pathway_tpu_exchange_rounds counter")
            lines.append(f"pathway_tpu_exchange_rounds {cst['rounds']}")
        sup = getattr(self.runtime, "supervisor", None)
        if sup is not None and sup.entries:
            # connector supervision counters (engine/supervisor.py):
            # restarts performed and a failed flag per source — the alerting
            # surface for degraded-but-serving pipelines
            lines.append("# TYPE pathway_tpu_connector_restarts counter")
            lines.append("# TYPE pathway_tpu_connector_failed gauge")
            for s in sup.summary():
                labels = f'{{source="{esc(s["source"])}"}}'
                lines.append(
                    f"pathway_tpu_connector_restarts{labels} {s['restarts']}")
                failed = 1 if s["state"] == "failed" else 0
                lines.append(
                    f"pathway_tpu_connector_failed{labels} {failed}")
        sched = self.runtime.scheduler
        bridge = sched.bridge_stats() if hasattr(sched, "bridge_stats") \
            else None
        if bridge is not None:
            # pipelined-execution instrumentation (engine/device_bridge.py):
            # in-flight depth + dispatch-queue wait make the host/device
            # overlap visible instead of inferred
            lines.append("# TYPE pathway_tpu_device_inflight_depth gauge")
            lines.append(
                f"pathway_tpu_device_inflight_depth {bridge['depth']}")
            lines.append("# TYPE pathway_tpu_device_inflight_window gauge")
            lines.append(f"pathway_tpu_device_inflight_window "
                         f"{bridge['max_inflight']}")
            lines.append("# TYPE pathway_tpu_device_legs_dispatched counter")
            lines.append(f"pathway_tpu_device_legs_dispatched "
                         f"{bridge['legs_dispatched']}")
            lines.append("# TYPE pathway_tpu_device_legs_resolved counter")
            lines.append(f"pathway_tpu_device_legs_resolved "
                         f"{bridge['legs_resolved']}")
            lines.append("# TYPE pathway_tpu_device_legs_overlapped counter")
            lines.append(f"pathway_tpu_device_legs_overlapped "
                         f"{bridge['legs_overlapped']}")
            lines.append(
                "# TYPE pathway_tpu_device_queue_wait_ms_total counter")
            lines.append(f"pathway_tpu_device_queue_wait_ms_total "
                         f"{bridge['queue_wait_ms']}")
            lines.append("# TYPE pathway_tpu_device_exec_ms_total counter")
            lines.append(
                f"pathway_tpu_device_exec_ms_total {bridge['exec_ms']}")
        limiter = getattr(self.runtime, "_backpressure", None)
        if limiter is not None:
            # the ingest budget of a runtime with no QoS armed
            # (engine/qos.py DeviceBackpressure): the rows a drain is held
            # to while the device is the slower side (0 while no bound
            # stands), the documents that fill one dispatch, under which a
            # bound never stands, and the looks at which that floor and
            # not milliseconds a row set the bound
            lines.append("# TYPE pathway_tpu_ingest_bound_rows gauge")
            lines.append(f"pathway_tpu_ingest_bound_rows "
                         f"{limiter.ingest_row_budget() or 0}")
            lines.append("# TYPE pathway_tpu_ingest_bound_floor_rows gauge")
            lines.append(f"pathway_tpu_ingest_bound_floor_rows "
                         f"{limiter.rows_per_dispatch()}")
            lines.append(
                "# TYPE pathway_tpu_ingest_bound_floored_total counter")
            lines.append(f"pathway_tpu_ingest_bound_floored_total "
                         f"{limiter.floored_looks}")
        woken = getattr(self.runtime, "ticks_woken_by", None)
        if woken is not None:
            # commit ticks by what ended the loop's wait
            # (engine/streaming.py): the autocommit period, or a request
            # pushed into a serving source before it ran out
            lines.append("# TYPE pathway_tpu_ticks_total counter")
            for cause, n in woken.items():
                lines.append(
                    f'pathway_tpu_ticks_total{{woken_by="{cause}"}} {n}')
        prof = _profiler_stats()
        if prof is not None:
            # continuous profiling plane (engine/profiler.py): rolling
            # MFU / HBM bandwidth utilization from the shared analytic
            # cost model (the same math bench.py reports), per-family
            # device time + arithmetic intensity, and the host sampler's
            # self-accounting (its <2% overhead contract, measurable)
            # the utilization gauges exist only where the device's peaks
            # are known (profiler.DEVICE_PEAKS) — absent, never defaulted
            rated = prof["machine"] is not None
            if rated:
                lines.append("# TYPE pathway_tpu_mfu_rolling gauge")
                lines.append(
                    f"pathway_tpu_mfu_rolling {prof['mfu_rolling']}")
                lines.append("# TYPE pathway_tpu_hbm_bw_util gauge")
                lines.append(
                    f"pathway_tpu_hbm_bw_util {prof['hbm_bw_util']}")
            fams = prof["families"]
            if fams:
                lines.append("# TYPE pathway_tpu_kernel_device_ms counter")
                lines.append("# TYPE pathway_tpu_kernel_dispatches counter")
                if rated:
                    lines.append("# TYPE pathway_tpu_kernel_mfu gauge")
                lines.append("# TYPE pathway_tpu_kernel_arithmetic_intensity"
                             " gauge")
                for fam, st in sorted(fams.items()):
                    flab = f'{{family="{esc(fam)}"}}'
                    lines.append(f"pathway_tpu_kernel_device_ms{flab} "
                                 f"{st['device_ms_total']}")
                    lines.append(f"pathway_tpu_kernel_dispatches{flab} "
                                 f"{st['dispatches']}")
                    if rated:
                        lines.append(
                            f"pathway_tpu_kernel_mfu{flab} {st['mfu']}")
                    lines.append(
                        f"pathway_tpu_kernel_arithmetic_intensity{flab} "
                        f"{st['roofline']['arithmetic_intensity']}")
            host = prof["host"]
            lines.append("# TYPE pathway_tpu_profiler_samples counter")
            lines.append(
                f"pathway_tpu_profiler_samples {host['samples_total']}")
            lines.append("# TYPE pathway_tpu_profiler_device_attributed"
                         "_samples counter")
            lines.append(f"pathway_tpu_profiler_device_attributed_samples "
                         f"{host['device_attributed_samples']}")
            lines.append("# TYPE pathway_tpu_profiler_overhead_ratio gauge")
            lines.append(f"pathway_tpu_profiler_overhead_ratio "
                         f"{host['overhead_ratio']}")
            lines.append("# TYPE pathway_tpu_profiler_distinct_stacks gauge")
            lines.append(f"pathway_tpu_profiler_distinct_stacks "
                         f"{host['distinct_stacks']}")
        try:
            from pathway_tpu.internals.autojit import autojit_stats

            ajs = autojit_stats()
        except Exception:
            ajs = None
        if ajs is not None:
            # auto-jit tier (internals/autojit.py): fused traceable-UDF
            # programs, XLA bucket compiles, loud-once demotions and the
            # per-backend dispatch counters — the evidence surface for
            # "the Table-path tax went into fused dispatches"
            lines.append("# TYPE pathway_tpu_autojit_enabled gauge")
            lines.append("pathway_tpu_autojit_enabled "
                         f"{1 if ajs['enabled'] else 0}")
            lines.append("# TYPE pathway_tpu_autojit_programs gauge")
            lines.append(f"pathway_tpu_autojit_programs {ajs['programs']}")
            lines.append("# TYPE pathway_tpu_autojit_compiles counter")
            lines.append(f"pathway_tpu_autojit_compiles {ajs['compiles']}")
            lines.append("# TYPE pathway_tpu_autojit_demotions counter")
            lines.append(
                f"pathway_tpu_autojit_demotions {ajs['demotions']}")
            lines.append(
                "# TYPE pathway_tpu_autojit_device_dispatches counter")
            lines.append(f"pathway_tpu_autojit_device_dispatches "
                         f"{ajs['device_dispatches']}")
            lines.append(
                "# TYPE pathway_tpu_autojit_vector_dispatches counter")
            lines.append(f"pathway_tpu_autojit_vector_dispatches "
                         f"{ajs['vector_dispatches']}")
            lines.append(
                "# TYPE pathway_tpu_autojit_fallback_batches counter")
            lines.append(f"pathway_tpu_autojit_fallback_batches "
                         f"{ajs['fallback_batches']}")
        persistence = getattr(self.runtime, "persistence", None)
        if persistence is not None:
            # commit-watermark durability (engine/persistence.py): lag
            # between the pipeline head and the durability frontier, the
            # bridge depth each commit trailed behind, per-commit durable
            # write latency, and transient-write retries — the surfaces
            # that make "checkpoints independent of in-flight depth"
            # checkable instead of asserted
            pst = persistence.stats()
            lines.append(
                "# TYPE pathway_tpu_commit_watermark_lag_ticks gauge")
            lines.append(f"pathway_tpu_commit_watermark_lag_ticks "
                         f"{pst['lag_ticks']}")
            lines.append("# TYPE pathway_tpu_commit_watermark gauge")
            lines.append(
                f"pathway_tpu_commit_watermark {pst['watermark']}")
            lines.append(
                "# TYPE pathway_tpu_device_inflight_at_commit gauge")
            lines.append(f"pathway_tpu_device_inflight_at_commit "
                         f"{pst['inflight_at_commit']}")
            lines.append("# TYPE pathway_tpu_persistence_commits counter")
            lines.append(
                f"pathway_tpu_persistence_commits {pst['commits']}")
            lines.append(
                "# TYPE pathway_tpu_persistence_entries_committed counter")
            lines.append(f"pathway_tpu_persistence_entries_committed "
                         f"{pst['entries_committed']}")
            lines.append(
                "# TYPE pathway_tpu_persistence_write_retries counter")
            lines.append(f"pathway_tpu_persistence_write_retries "
                         f"{pst['write_retries']}")
            # snapshot tier (bounded-time recovery): age names a wedged
            # snapshot loop, wal_replayable_entries is the restart cost
            # compaction bounds, compactions prove truncation happens
            lines.append("# TYPE pathway_tpu_snapshot_age_ticks gauge")
            lines.append(f"pathway_tpu_snapshot_age_ticks "
                         f"{pst['snapshot_age_ticks']}")
            lines.append("# TYPE pathway_tpu_snapshot_bytes gauge")
            lines.append(
                f"pathway_tpu_snapshot_bytes {pst['snapshot_bytes']}")
            lines.append("# TYPE pathway_tpu_snapshot_generation gauge")
            lines.append(f"pathway_tpu_snapshot_generation "
                         f"{pst['snapshot_generation']}")
            lines.append("# TYPE pathway_tpu_snapshots_total counter")
            lines.append(
                f"pathway_tpu_snapshots_total {pst['snapshots_total']}")
            lines.append("# TYPE pathway_tpu_compactions_total counter")
            lines.append(
                f"pathway_tpu_compactions_total {pst['compactions_total']}")
            lines.append(
                "# TYPE pathway_tpu_wal_replayable_entries gauge")
            lines.append(f"pathway_tpu_wal_replayable_entries "
                         f"{pst['wal_replayable_entries']}")
            # write-path failover (PR 18): the fencing epoch this driver
            # holds and the writes it REFUSED as a fenced stale primary
            # — a resumed zombie shows as fenced_writes climbing while
            # its epoch gauge stays below the fleet's
            lines.append("# TYPE pathway_tpu_fleet_epoch gauge")
            lines.append(
                f"pathway_tpu_fleet_epoch {pst.get('fencing_epoch', 0)}")
            lines.append("# TYPE pathway_tpu_fenced_writes_total counter")
            lines.append(f"pathway_tpu_fenced_writes_total "
                         f"{pst.get('fenced_writes', 0)}")
            lines.append("# TYPE pathway_tpu_commit_wait_ms histogram")
            for le, c in persistence.commit_wait.cumulative():
                le_s = "+Inf" if le == float("inf") else format(le, "g")
                lines.append(
                    f'pathway_tpu_commit_wait_ms_bucket{{le="{le_s}"}} {c}')
            lines.append(f"pathway_tpu_commit_wait_ms_sum "
                         f"{round(persistence.commit_wait.sum_ms, 6)}")
            lines.append(f"pathway_tpu_commit_wait_ms_count "
                         f"{persistence.commit_wait.count}")
        experts = _expert_load()
        if experts is not None:
            lines.append("# TYPE pathway_tpu_moe_tokens_per_expert_max gauge")
            lines.append(
                f"pathway_tpu_moe_tokens_per_expert_max {experts['max']}")
            lines.append(
                "# TYPE pathway_tpu_moe_tokens_per_expert_mean gauge")
            lines.append(
                f"pathway_tpu_moe_tokens_per_expert_mean {experts['mean']}")
            lines.append("# TYPE pathway_tpu_moe_dispatches counter")
            lines.append(
                f"pathway_tpu_moe_dispatches {experts['dispatches']}")
            # the routed experts' pair buffer (ops/moe.py): how often a
            # layer fell back to a buffer as long as every pair
            lines.append("# TYPE pathway_tpu_moe_full_buffer_layers counter")
            lines.append(f"pathway_tpu_moe_full_buffer_layers "
                         f"{experts['full_buffer_layers']}")
            lines.append("# TYPE pathway_tpu_moe_buffer_rows_mean gauge")
            lines.append(f"pathway_tpu_moe_buffer_rows_mean "
                         f"{experts['buffer_rows_mean']}")
            if "pairs" in experts:
                # a router with identity experts: the chosen (token,
                # expert) pairs that cost no product, of all chosen
                lines.append(
                    "# TYPE pathway_tpu_moe_zero_expert_pairs counter")
                lines.append(f"pathway_tpu_moe_zero_expert_pairs "
                             f"{experts['zero_pairs']}")
                lines.append("# TYPE pathway_tpu_moe_pairs counter")
                lines.append(f"pathway_tpu_moe_pairs {experts['pairs']}")
            if "visible_pairs" in experts:
                # a model that chooses the keys a query attends over: the
                # (query, key) pairs attended, of those visible (the
                # device's own counts, every attention layer)
                lines.append(
                    "# TYPE pathway_tpu_attention_pairs_selected counter")
                lines.append(f"pathway_tpu_attention_pairs_selected "
                             f"{experts['selected_pairs']}")
                lines.append(
                    "# TYPE pathway_tpu_attention_pairs_visible counter")
                lines.append(f"pathway_tpu_attention_pairs_visible "
                             f"{experts['visible_pairs']}")
        scans = _scan_lowerings()
        if scans is not None:
            # which lowering the delta-rule scans of the compiled programs
            # took: the fused TPU kernel, or the reference (every other
            # backend, and the shapes the kernel does not tile)
            lines.append("# TYPE pathway_tpu_deltanet_scan_programs counter")
            for lowering, count in sorted(scans.items()):
                lines.append(f'pathway_tpu_deltanet_scan_programs'
                             f'{{lowering="{lowering}"}} {count}')
        attention, tiles = _attention_stats()
        if attention is not None:
            # which lowering the decoder's blocked attention took in the
            # compiled programs: the Pallas TPU kernel, or plain JAX
            lines.append("# TYPE pathway_tpu_attention_programs counter")
            for lowering, count in sorted(attention.items()):
                lines.append(f'pathway_tpu_attention_programs'
                             f'{{lowering="{lowering}"}} {count}')
        if tiles is not None:
            # key blocks the attention layers ran, of all up to the
            # diagonal: what windows and documents' edges spare
            lines.append("# TYPE pathway_tpu_attention_tiles_run counter")
            lines.append(
                f"pathway_tpu_attention_tiles_run {tiles['tiles_run']}")
            lines.append("# TYPE pathway_tpu_attention_tiles_all counter")
            lines.append(
                f"pathway_tpu_attention_tiles_all {tiles['tiles_all']}")
        paged = _paged_stats()
        if paged is not None:
            # paged vector store occupancy (engine/paged_store.py): pool
            # totals + the free-list level that proves delete/ingest churn
            # reuses pages instead of growing HBM
            lines.append("# TYPE pathway_tpu_paged_page_rows gauge")
            lines.append(f"pathway_tpu_paged_page_rows {paged['page_rows']}")
            lines.append("# TYPE pathway_tpu_paged_pages_total gauge")
            lines.append(
                f"pathway_tpu_paged_pages_total {paged['pages_total']}")
            lines.append("# TYPE pathway_tpu_paged_pages_free gauge")
            lines.append(
                f"pathway_tpu_paged_pages_free {paged['pages_free']}")
            lines.append("# TYPE pathway_tpu_paged_live_rows gauge")
            lines.append(f"pathway_tpu_paged_live_rows {paged['live_rows']}")
            lines.append("# TYPE pathway_tpu_paged_occupancy_ratio gauge")
            lines.append(f"pathway_tpu_paged_occupancy_ratio "
                         f"{round(paged['occupancy'], 6)}")
            lines.append("# TYPE pathway_tpu_paged_extents gauge")
            lines.append(f"pathway_tpu_paged_extents {paged['extents']}")
            lines.append("# TYPE pathway_tpu_paged_grow_events counter")
            lines.append(
                f"pathway_tpu_paged_grow_events {paged['grow_events']}")
            if paged["tenants"]:
                lines.append("# TYPE pathway_tpu_paged_tenant_pages gauge")
                for tenant, n in sorted(paged["tenants"].items()):
                    lines.append(
                        f'pathway_tpu_paged_tenant_pages'
                        f'{{tenant="{esc(tenant)}"}} {n}')
        rc = _cache_stats()
        if rc is not None:
            # semantic result cache (engine/result_cache.py): repeated
            # queries served without a kernel dispatch, invalidated
            # incrementally from the same deltas that maintain the index
            lines.append("# TYPE pathway_tpu_cache_hits counter")
            lines.append(f"pathway_tpu_cache_hits {rc['hits']}")
            lines.append("# TYPE pathway_tpu_cache_misses counter")
            lines.append(f"pathway_tpu_cache_misses {rc['misses']}")
            lines.append("# TYPE pathway_tpu_cache_invalidations counter")
            lines.append(
                f"pathway_tpu_cache_invalidations {rc['invalidations']}")
            lines.append("# TYPE pathway_tpu_cache_entries gauge")
            lines.append(f"pathway_tpu_cache_entries {rc['entries']}")
            lines.append("# TYPE pathway_tpu_cache_hit_ratio gauge")
            lines.append(
                f"pathway_tpu_cache_hit_ratio {round(rc['hit_ratio'], 6)}")
            lines.append("# TYPE pathway_tpu_cache_evictions counter")
            lines.append(f"pathway_tpu_cache_evictions {rc['evictions']}")
            lines.append("# TYPE pathway_tpu_cache_index_version gauge")
            lines.append(
                f"pathway_tpu_cache_index_version {rc['version']}")
            lines.append(
                "# TYPE pathway_tpu_cache_invalidations_per_tick gauge")
            lines.append(
                f"pathway_tpu_cache_invalidations_per_tick "
                f"{round(rc['invalidations_per_tick'], 6)}")
        promotions = getattr(self.runtime, "promotions", 0)
        if promotions:
            # this process was PROMOTED replica→primary (write-path
            # failover); the wall clock is promote-command → serving
            lines.append("# TYPE pathway_tpu_promotions_total counter")
            lines.append(f"pathway_tpu_promotions_total {promotions}")
            fp = getattr(self.runtime, "failover_promotion_s", None)
            if fp is not None:
                lines.append("# TYPE pathway_tpu_failover_seconds gauge")
                lines.append(
                    f"pathway_tpu_failover_seconds {round(fp, 6)}")
        replica = getattr(self.runtime, "replica", None)
        if replica is not None:
            # replica-fleet freshness (engine/replica.py): watermark lag
            # behind the primary, the applied frontier, and hydration
            # cost — the same families the router exports fleet-wide,
            # labeled with this replica's id
            rst = replica.stats()
            rlab = f'{{replica="{esc(rst["replica_id"])}"}}'
            lines.append(
                "# TYPE pathway_tpu_replica_staleness_ticks gauge")
            lines.append(f"pathway_tpu_replica_staleness_ticks{rlab} "
                         f"{rst['staleness_ticks']}")
            lines.append("# TYPE pathway_tpu_replica_applied_tick gauge")
            lines.append(f"pathway_tpu_replica_applied_tick{rlab} "
                         f"{rst['applied_tick']}")
            lines.append(
                "# TYPE pathway_tpu_replica_primary_watermark gauge")
            lines.append(f"pathway_tpu_replica_primary_watermark{rlab} "
                         f"{rst['primary_watermark']}")
            lines.append("# TYPE pathway_tpu_replica_generation gauge")
            lines.append(f"pathway_tpu_replica_generation{rlab} "
                         f"{rst['generation']}")
            lines.append(
                "# TYPE pathway_tpu_replica_entries_applied counter")
            lines.append(f"pathway_tpu_replica_entries_applied{rlab} "
                         f"{rst['entries_applied']}")
            if rst["hydrate_wall_s"] is not None:
                lines.append(
                    "# TYPE pathway_tpu_replica_hydrate_seconds gauge")
                lines.append(
                    f"pathway_tpu_replica_hydrate_seconds{rlab} "
                    f"{rst['hydrate_wall_s']}")
        try:
            import resource

            rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            lines.append("# TYPE pathway_tpu_process_memory_max_bytes gauge")
            lines.append(f"pathway_tpu_process_memory_max_bytes {rss_kb * 1024}")
        except Exception:
            pass
        lines.append("# EOF")
        return "\n".join(lines) + "\n"

    def profile_host_response(self, query: str) -> tuple[int, bytes, str]:
        """``/profile/host[?seconds=N]``: collapsed-flamegraph text
        (``role;frame;... count`` per line). With ``seconds``, snapshots
        the folded-stack counters, sleeps, and serves only the window's
        delta; capped at 60 s. 503 when no profiler is installed."""
        from pathway_tpu.engine.profiler import current_profiler

        prof = current_profiler()
        if prof is None:
            return (503, json.dumps(
                {"error": "profiler not running "
                          "(enable with PATHWAY_PROFILER=1)"}).encode(),
                "application/json")
        seconds = 0.0
        for part in query.split("&"):
            if part.startswith("seconds="):
                try:
                    seconds = min(60.0, max(0.0, float(part[8:])))
                except ValueError:
                    pass
        if seconds > 0.0:
            import time as _time

            baseline = prof.stack_counts()
            _time.sleep(seconds)
            text = prof.collapsed(baseline)
        else:
            text = prof.collapsed()
        return 200, text.encode(), "text/plain; charset=utf-8"

    def profile_device_response(self, start: bool,
                                query: str) -> tuple[int, dict]:
        """``/profile/device/start|stop``: drive an on-demand
        jax.profiler capture into an artifact directory (start accepts
        ``?dir=...``). 409 when starting twice / stopping idle, 503
        when no profiler is installed."""
        from pathway_tpu.engine.profiler import current_profiler

        prof = current_profiler()
        if prof is None:
            return 503, {"error": "profiler not running "
                                  "(enable with PATHWAY_PROFILER=1)"}
        try:
            if start:
                out_dir = None
                for part in query.split("&"):
                    if part.startswith("dir="):
                        from urllib.parse import unquote

                        out_dir = unquote(part[4:])
                return 200, {"capturing": True,
                             "dir": prof.start_device_capture(out_dir)}
            return 200, {"capturing": False,
                         "dir": prof.stop_device_capture()}
        except RuntimeError as e:
            return 409, {"error": str(e)}
        except Exception as e:  # jax.profiler unavailable / backend error
            return 503, {"error": f"{type(e).__name__}: {e}"}

    # -- server ------------------------------------------------------------
    def start(self) -> None:
        server = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):
                code = 200
                path, _sep, query = self.path.partition("?")
                path = path.rstrip("/")
                if path in ("", "/status"):
                    body = json.dumps(server.status_payload()).encode()
                    ctype = "application/json"
                elif path == "/metrics":
                    body = server.metrics_payload().encode()
                    ctype = "text/plain; version=0.0.4"
                elif path == "/healthz":
                    healthy, payload = server.healthz_payload()
                    body = json.dumps(payload).encode()
                    ctype = "application/json"
                    code = 200 if healthy else 503
                elif path == "/trace":
                    # ?format=chrome: the fleet-mergeable Chrome trace
                    # payload (engine/fleet_observability.py)
                    if "format=chrome" in query:
                        payload = server.chrome_trace_payload()
                    else:
                        payload = server.trace_payload()
                    body = json.dumps(payload).encode()
                    ctype = "application/json"
                elif path == "/profile/host":
                    # collapsed-flamegraph text (engine/profiler.py):
                    # ?seconds=N windows the profile to samples taken
                    # from now (each request has its own handler thread,
                    # so the sleep blocks nobody else)
                    code, body, ctype = server.profile_host_response(query)
                elif path in ("/profile/device/start",
                              "/profile/device/stop"):
                    code, payload = server.profile_device_response(
                        path.endswith("/start"), query)
                    body = json.dumps(payload).encode()
                    ctype = "application/json"
                else:
                    self.send_response(404)
                    self.end_headers()
                    return
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):
                pass

        self._httpd = ThreadingHTTPServer(("127.0.0.1", self.port), Handler)
        self.port = self._httpd.server_address[1]
        from pathway_tpu.engine.threads import spawn

        self._thread = spawn(self._httpd.serve_forever, name="http")

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
