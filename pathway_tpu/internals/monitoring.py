"""Live monitoring dashboard.

Reference: python/pathway/internals/monitoring.py:56-226 — a rich-based
Live terminal dashboard showing per-connector/operator rows (insertions,
retractions, latency) above a rolling log panel, refreshed in place while
the pipeline runs, gated by ``MonitoringLevel``. Latency comes from the
scheduler's per-operator step probes (engine/graph.py Scheduler.stats,
the analogue of OperatorStats fed by Probers,
src/engine/progress_reporter.rs:114).
"""

from __future__ import annotations

import collections
import enum
import logging
import sys
import time


class MonitoringLevel(enum.Enum):
    AUTO = enum.auto()
    AUTO_ALL = enum.auto()
    NONE = enum.auto()
    IN_OUT = enum.auto()
    ALL = enum.auto()


def _log_buffer_lines(default: int = 8) -> int:
    """Log-panel depth, overridable with PATHWAY_LOG_BUFFER_LINES (a
    post-mortem dump in the log pane needs more than 8 lines)."""
    from pathway_tpu.internals.config import _env_int

    return max(1, _env_int("PATHWAY_LOG_BUFFER_LINES", default))


class _LogBuffer(logging.Handler):
    """Captures recent log records for the dashboard's log panel
    (reference keeps a rich log pane under the stats table)."""

    def __init__(self, maxlen: int | None = None):
        super().__init__()
        if maxlen is None:
            maxlen = _log_buffer_lines()
        self.records: collections.deque[str] = collections.deque(
            maxlen=maxlen)

    def emit(self, record):
        try:
            self.records.append(self.format(record))
        except Exception:
            pass


class StatsMonitor:
    """Collects per-operator counters + latency from the scheduler and
    renders a live terminal dashboard (rich Live on a tty, plain lines
    otherwise)."""

    def __init__(self, level: MonitoringLevel = MonitoringLevel.NONE,
                 refresh_seconds: float = 1.0):
        self.level = level
        self.refresh_seconds = refresh_seconds
        self._last_render = 0.0
        self._live = None
        self._rows: list[tuple] = []
        self._t0 = time.monotonic()
        # persistence driver (engine/persistence.py), set by the runtime:
        # the durability panel shows the commit watermark trailing the
        # pipeline before the lag ever becomes a stall
        self.persistence = None
        # connector supervision state (engine/supervisor.py) rendered as a
        # second panel: per-source lifecycle, restart counts, last error
        self.supervisor = None
        self._log = _LogBuffer()
        self._log.setFormatter(logging.Formatter("%(levelname)s %(message)s"))
        if self.enabled():
            logging.getLogger().addHandler(self._log)

    def set_supervisor(self, supervisor) -> None:
        self.supervisor = supervisor

    def enabled(self) -> bool:
        if self.level == MonitoringLevel.NONE:
            return False
        if self.level in (MonitoringLevel.AUTO, MonitoringLevel.AUTO_ALL):
            return sys.stderr.isatty()
        return True

    def _in_out_only(self) -> bool:
        return self.level in (MonitoringLevel.IN_OUT, MonitoringLevel.AUTO)

    def update(self, scheduler, graph, now_time: int) -> None:
        if not self.enabled():
            return
        now = time.monotonic()
        if now - self._last_render < self.refresh_seconds:
            return
        self._last_render = now
        self._rows = []
        # serving panel: per-request SLO snapshot from the run's request
        # tracker (engine/request_tracker.py) — query quantiles, burn
        # rate and the most recent over-budget request's dominant stage
        self._serving_lines = self._serving_panel(scheduler)
        # QoS panel: the control loop's side of the serving story —
        # budget partition, admission queue, shed/deferral/coalescing
        # (engine/qos.py)
        self._qos_line = self._qos_panel()
        # paged vector store line: page occupancy, free-list level and
        # growth events (engine/paged_store.py) — page churn and online
        # growth are visible without scraping /metrics
        self._paged_line = self._paged_panel()
        # semantic result cache line: hit ratio, entry count and the
        # incremental-invalidation counters (engine/result_cache.py)
        self._cache_line = self._cache_panel()
        # profiler line: rolling MFU / HBM bandwidth utilisation from the
        # device cost model plus the host sampler's hottest frame and its
        # own overhead ratio (engine/profiler.py)
        self._profiler_line = self._profiler_panel()
        # durability line: commit watermark, its lag behind the pipeline
        # head, and the bridge depth the last commit trailed — a frozen
        # watermark is visible here before the watchdog fires
        self._persistence_line = None
        if self.persistence is not None:
            pst = self.persistence.stats()
            self._persistence_line = (
                f"commit watermark t={pst['watermark']}  "
                f"lag {pst['lag_ticks']} tick(s)  "
                f"commits {pst['commits_with_data']}/{pst['commits']}  "
                f"inflight@commit {pst['inflight_at_commit']}  "
                f"wait {pst['commit_wait_ms_sum']:.0f}ms  "
                f"write-retries {pst['write_retries']}")
            if pst.get("snapshot_generation"):
                # snapshot tier: generation + age make a wedged snapshot
                # loop visible next to the (healthy) commit watermark
                self._persistence_line += (
                    f"  snap gen {pst['snapshot_generation']} "
                    f"t={pst['snapshot_tick']} "
                    f"age {pst['snapshot_age_ticks']}  "
                    f"wal {pst['wal_replayable_entries']} entr.")
        # pipelined-execution line: in-flight depth, dispatch-queue wait
        # and overlap ratio straight from the device bridge, so the
        # host/device overlap is observable, not inferred
        self._bridge_line = None
        bridge = scheduler.bridge_stats() \
            if hasattr(scheduler, "bridge_stats") else None
        if bridge is not None:
            self._bridge_line = (
                f"device bridge: in-flight {bridge['depth']}/"
                f"{bridge['max_inflight']}  legs {bridge['legs_resolved']}/"
                f"{bridge['legs_dispatched']}  "
                f"overlap {bridge['overlap_ratio']:.0%}  "
                f"queue-wait {bridge['queue_wait_ms']:.0f}ms  "
                f"exec {bridge['exec_ms']:.0f}ms")
        # fused-program dispatches (internals/autojit.py): the pipelining
        # panel shows whether the auto-jit tier is carrying batches and
        # on which backend — a demotion is visible here live
        try:
            from pathway_tpu.internals.autojit import autojit_stats

            ajs = autojit_stats()
        except Exception:
            ajs = None
        if ajs is not None and ajs["programs"]:
            line = (
                f"auto-jit: {ajs['programs']} fused program(s)  "
                f"xla {ajs['device_dispatches']} / "
                f"vector {ajs['vector_dispatches']} dispatches  "
                f"compiles {ajs['compiles']}  "
                f"demotions {ajs['demotions']}")
            self._bridge_line = (f"{self._bridge_line}\n{line}"
                                 if self._bridge_line else line)
        for node in graph.nodes:
            st = scheduler.stats.get(node.id)
            if not st:
                continue
            if self._in_out_only() and not node.name.startswith(
                    ("source", "subscribe", "capture", "output")):
                continue
            self._rows.append((node.name or str(node.id),
                               st["insertions"], st["retractions"],
                               st.get("latency_ms", 0.0),
                               st.get("total_ms", 0.0)))
        self._render(now_time)

    def _renderable(self, now_time: int):
        from rich.console import Group
        from rich.panel import Panel
        from rich.table import Table as RichTable

        elapsed = time.monotonic() - self._t0
        table = RichTable(
            title=f"pathway-tpu  t={now_time}  up {elapsed:5.1f}s")
        table.add_column("operator")
        table.add_column("insertions", justify="right")
        table.add_column("retractions", justify="right")
        table.add_column("latency ms", justify="right")
        table.add_column("total ms", justify="right")
        for name, ins, rets, lat, tot in self._rows:
            table.add_row(name, str(ins), str(rets), f"{lat:.2f}",
                          f"{tot:.0f}")
        parts = [table]
        slow = self._slowest_lines()
        if slow:
            parts.append(Panel("\n".join(slow), title="top slowest (last tick)",
                               height=None))
        if getattr(self, "_bridge_line", None):
            parts.append(Panel(self._bridge_line, title="pipelining",
                               height=None))
        if getattr(self, "_persistence_line", None):
            parts.append(Panel(self._persistence_line, title="durability",
                               height=None))
        if getattr(self, "_paged_line", None):
            parts.append(Panel(self._paged_line, title="paged store",
                               height=None))
        if getattr(self, "_cache_line", None):
            parts.append(Panel(self._cache_line, title="result cache",
                               height=None))
        if getattr(self, "_profiler_line", None):
            parts.append(Panel(self._profiler_line, title="profiler",
                               height=None))
        if getattr(self, "_serving_lines", None):
            parts.append(Panel("\n".join(self._serving_lines),
                               title="serving", height=None))
        if getattr(self, "_qos_line", None):
            parts.append(Panel(self._qos_line, title="qos", height=None))
        sup_lines = self._supervisor_lines()
        if sup_lines:
            parts.append(Panel("\n".join(sup_lines), title="connectors",
                               height=None))
        if self._log.records:
            parts.append(Panel("\n".join(self._log.records), title="log",
                               height=None))
        return parts[0] if len(parts) == 1 else Group(*parts)

    def _serving_panel(self, scheduler) -> list[str]:
        rec = getattr(scheduler, "recorder", None)
        tracker = rec.requests if rec is not None and rec.enabled else None
        if tracker is None or not tracker.count:
            return []
        s = tracker.summary()
        lines = []
        e2e = s.get("e2e_ms")
        if e2e:
            lines.append(
                f"queries {s['requests']}  p50 {e2e['p50']:.1f}ms  "
                f"p95 {e2e['p95']:.1f}ms  p99 {e2e['p99']:.1f}ms  "
                f"SLO {s['slo_ms']:.0f}ms  burn {s['burn_rate']:.2f}x  "
                f"over-budget {s['violations']}")
        stages = s.get("stages")
        if stages:
            lines.append("stage p50: " + "  ".join(
                f"{name} {v:.1f}ms" for name, v in stages.items()
                if v is not None))
        slow = tracker.slow_queries()
        if slow:
            last = slow[-1]
            lines.append(
                f"slow: {last['request_id']} {last['e2e_ms']:.1f}ms "
                f"dominant {last['dominant_stage']} "
                f"({last['stages'][last['dominant_stage']]:.1f}ms)")
        return lines

    def _qos_panel(self) -> str | None:
        try:
            from pathway_tpu.engine.qos import current_controller

            ctl = current_controller()
        except Exception:
            return None
        if ctl is None:
            return None
        s = ctl.summary()
        line = (f"{s['mode']}: query budget {s['query_budget_ms']:.1f}ms  "
                f"ingest {s['ingest_rows_per_tick']} rows/tick  "
                f"queue {s['admission_queue_depth']}/"
                f"{s['admission_queue_cap']}  shed {s['shed_total']}  "
                f"deferrals {s['ingest_deferrals']}  "
                f"coalesced {s['coalesced_queries']}q/"
                f"{s['coalesced_dispatches']}d")
        if s["shedding"]:
            line += "  SHEDDING"
        if s["backpressure_active"]:
            line += "  backpressure"
        return line

    def _paged_panel(self) -> str | None:
        try:
            from pathway_tpu.engine.paged_store import live_paged_stats

            st = live_paged_stats()
        except Exception:
            return None
        if st is None:
            return None
        line = (f"pages {st['pages_total'] - st['pages_free']}/"
                f"{st['pages_total']} x {st['page_rows']} rows  "
                f"occupancy {st['occupancy']:.0%}  "
                f"extents {st['extents']}  grows {st['grow_events']}")
        if st["tenants"]:
            line += "  tenants " + " ".join(
                f"{t}:{n}p" for t, n in sorted(st["tenants"].items()))
        return line

    def _cache_panel(self) -> str | None:
        try:
            from pathway_tpu.engine.result_cache import live_cache_stats

            st = live_cache_stats()
        except Exception:
            return None
        if st is None:
            return None
        return (f"entries {st['entries']}  "
                f"hit {st['hit_ratio']:.0%} ({st['hits']}h/{st['misses']}m)"
                f"  invalidations {st['invalidations']} "
                f"({st['invalidations_per_tick']:.2f}/tick)  "
                f"v{st['version']}")

    def _profiler_panel(self) -> str | None:
        try:
            from pathway_tpu.engine.profiler import live_profiler_stats

            st = live_profiler_stats()
        except Exception:
            return None
        if st is None:
            return None
        if st["machine"] is None:  # no peaks for this device: no rating
            line = "MFU/HBM not measured  "
        else:
            line = (f"MFU {st['mfu_rolling']:.1%}  "
                    f"HBM {st['hbm_bw_util']:.1%}  ")
        line += (f"samples {st['host']['samples_total']} "
                 f"({st['host']['device_attributed_samples']} on-device)  "
                 f"overhead {st['host']['overhead_ratio']:.2%}")
        top = st["host"].get("top_frame")
        if top:
            line += f"\nhot: {top}"
        fams = st.get("families") or {}
        bound = [f"{name}:{fam['roofline']['bound_by'][:4]}"
                 for name, fam in sorted(fams.items())
                 if fam["dispatches"] and fam["roofline"]["bound_by"]]
        if bound:
            line += "\nroofline " + "  ".join(bound)
        return line

    def _slowest_lines(self, top_n: int = 5) -> list[str]:
        """Critical-path panel: the operators that dominated the last
        tick, worst first — the per-tick answer to "where does the time
        go" (stats latency_ms is each operator's last step latency)."""
        ranked = sorted(self._rows, key=lambda r: r[3], reverse=True)
        total = sum(r[3] for r in self._rows) or 1.0
        lines = []
        for name, _ins, _rets, lat, _tot in ranked[:top_n]:
            if lat <= 0.0:
                break
            lines.append(f"{name}: {lat:.2f}ms ({lat / total:.0%} of tick)")
        return lines

    def _supervisor_lines(self) -> list[str]:
        if self.supervisor is None:
            return []
        lines = []
        for s in self.supervisor.summary():
            line = (f"{s['source']}: {s['state']}  rows={s['forwarded']}  "
                    f"restarts={s['restarts']}")
            if s["restarts"] and s.get("last_restart_age_s") is not None:
                line += f" (last {s['last_restart_age_s']:.0f}s ago)"
            if s["stalled"]:
                line += "  STALLED"
            if s["error"]:
                line += f"  last_error={s['error']}"
            lines.append(line)
        if self.supervisor.commit_stalled:
            lines.append("COMMIT LOOP STALLED (watchdog)")
        return lines

    def _render(self, now_time: int) -> None:
        try:
            if self._live is None:
                from rich.console import Console
                from rich.live import Live

                self._live = Live(self._renderable(now_time),
                                  console=Console(stderr=True),
                                  refresh_per_second=4, transient=False)
                self._live.start()
            else:
                self._live.update(self._renderable(now_time))
        except Exception:
            for name, ins, rets, lat, tot in self._rows:
                print(f"[monitor] {name}: +{ins} -{rets} {lat:.2f}ms",
                      file=sys.stderr)
            if getattr(self, "_bridge_line", None):
                print(f"[monitor] {self._bridge_line}", file=sys.stderr)
            if getattr(self, "_persistence_line", None):
                print(f"[monitor] {self._persistence_line}", file=sys.stderr)
            if getattr(self, "_paged_line", None):
                print(f"[monitor] {self._paged_line}", file=sys.stderr)
            if getattr(self, "_cache_line", None):
                print(f"[monitor] {self._cache_line}", file=sys.stderr)
            if getattr(self, "_profiler_line", None):
                print(f"[monitor] {self._profiler_line}", file=sys.stderr)
            for line in getattr(self, "_serving_lines", None) or ():
                print(f"[monitor] {line}", file=sys.stderr)
            if getattr(self, "_qos_line", None):
                print(f"[monitor] {self._qos_line}", file=sys.stderr)
            for line in self._supervisor_lines():
                print(f"[monitor] {line}", file=sys.stderr)

    def close(self) -> None:
        if self._live is not None:
            try:
                self._live.stop()
            except Exception:
                pass
            self._live = None
        if self.enabled():
            logging.getLogger().removeHandler(self._log)
