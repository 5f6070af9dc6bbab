"""HTTP monitoring endpoint + error-trace attribution
(reference: src/engine/http_server.rs, internals/trace.py), plus the
exposition-format contract of every /metrics family: label escaping,
histogram bucket monotonicity + _sum/_count consistency, and a regex lint
over every emitted line."""

from __future__ import annotations

import json
import math
import re
import urllib.request

import pytest

import pathway_tpu as pw
from pathway_tpu.engine.http_server import MonitoringHttpServer
from pathway_tpu.internals.parse_graph import G


@pytest.fixture(autouse=True)
def fresh_graph():
    G.clear()
    yield
    G.clear()


class _FakeNode:
    def __init__(self, id, name):
        self.id = id
        self.name = name
        self.op = object()
        self.trace = None


class _FakeRuntime:
    def __init__(self):
        class Sched:
            stats = {0: {"insertions": 7, "retractions": 2}}
            recorder = None

        class Graph:
            nodes = [_FakeNode(0, "source:test")]

        class Runner:
            graph = Graph()

        self.scheduler = Sched()
        self.runner = Runner()
        self.sessions = [1, 2]


_AWKWARD = 'source:"we\\ird"\nname'  # quote, backslash, newline

_STEP_SAMPLES_MS = (0.05, 0.3, 2.0, 7.0, 180.0, 3000.0, 50_000.0)


def _recording_runtime():
    """A fake runtime whose scheduler carries a flight recorder with one
    awkwardly-named operator and a known latency sample set."""
    from pathway_tpu.engine.flight_recorder import FlightRecorder

    rt = _FakeRuntime()
    rec = FlightRecorder()
    rec.enabled = True
    node = _FakeNode(0, _AWKWARD)
    for i, ms in enumerate(_STEP_SAMPLES_MS):
        rec.record(i, node, "host", float(i), ms, 10, 9)
    rt.scheduler.recorder = rec
    return rt


def test_http_status_and_metrics():
    server = MonitoringHttpServer(_FakeRuntime(), port=0)  # ephemeral port
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        status = json.loads(urllib.request.urlopen(base + "/status").read())
        assert status["sources"] == 2
        assert status["operators"][0]["insertions"] == 7
        metrics = urllib.request.urlopen(base + "/metrics").read().decode()
        assert 'pathway_tpu_insertions{operator="source:test",id="0"} 7' in metrics
        assert metrics.rstrip().endswith("# EOF")
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# /metrics exposition format: escaping, histogram invariants, family lint
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^(?P<family>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{(?P<labels>(?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*",?)+)\})?'
    r' (?P<value>-?(?:[0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?|\+Inf|NaN))$')


def _metrics_lines(rt) -> list[str]:
    server = MonitoringHttpServer(rt, port=0)
    return server.metrics_payload().splitlines()


def _parse_samples(lines):
    """[(family, {label: value}, float)] for every sample line; asserts
    every non-comment line parses (the regex lint)."""
    out = []
    for line in lines:
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        labels = {}
        raw = m.group("labels")
        if raw:
            for lm in re.finditer(r'([a-zA-Z_][a-zA-Z0-9_]*)='
                                  r'"((?:[^"\\\n]|\\.)*)"', raw):
                labels[lm.group(1)] = lm.group(2)
        out.append((m.group("family"), labels, float(m.group("value"))))
    return out


def test_metrics_regex_lint_every_family_typed():
    """Every emitted sample parses, and every family is announced with a
    # TYPE line (histogram samples resolve to their base family)."""
    lines = _metrics_lines(_recording_runtime())
    assert lines[-1] == "# EOF"
    typed = {l.split()[2] for l in lines if l.startswith("# TYPE")}
    assert typed, "no TYPE declarations emitted"
    for family, _labels, _v in _parse_samples(lines):
        base = re.sub(r"_(bucket|sum|count)$", "", family)
        assert family in typed or base in typed, \
            f"sample family {family!r} has no # TYPE declaration"


def test_metrics_label_escaping_round_trips():
    """Quote / backslash / newline in an operator name must be escaped per
    the exposition format and decode back to the original name."""
    lines = _metrics_lines(_recording_runtime())
    ops = set()
    for family, labels, _v in _parse_samples(lines):
        if family.startswith("pathway_tpu_operator_step_duration_ms"):
            raw = labels["operator"]
            assert "\n" not in raw
            ops.add(raw.replace(r"\\", "\x00").replace(r"\"", '"')
                    .replace(r"\n", "\n").replace("\x00", "\\"))
    assert _AWKWARD in ops


def test_histogram_monotonic_and_sum_count_consistent():
    lines = _metrics_lines(_recording_runtime())
    buckets = []   # (le, cumulative_count) in emission order
    sum_ms = count = None
    for family, labels, v in _parse_samples(lines):
        if family == "pathway_tpu_operator_step_duration_ms_bucket":
            le = math.inf if labels["le"] == "+Inf" else float(labels["le"])
            buckets.append((le, v))
        elif family == "pathway_tpu_operator_step_duration_ms_sum":
            sum_ms = v
        elif family == "pathway_tpu_operator_step_duration_ms_count":
            count = v
    assert buckets and sum_ms is not None and count is not None
    # le values strictly increasing, ending at +Inf
    les = [b[0] for b in buckets]
    assert les == sorted(les) and len(set(les)) == len(les)
    assert les[-1] == math.inf
    # cumulative counts monotonically non-decreasing; +Inf == _count
    counts = [b[1] for b in buckets]
    assert counts == sorted(counts)
    assert counts[-1] == count == len(_STEP_SAMPLES_MS)
    assert sum_ms == pytest.approx(sum(_STEP_SAMPLES_MS), rel=1e-6)
    # spot-check one interior bucket: samples <= 2.5ms
    by_le = dict(buckets)
    assert by_le[2.5] == sum(1 for ms in _STEP_SAMPLES_MS if ms <= 2.5)


def test_metrics_row_counters_and_gauges_still_linted():
    """The pre-existing families (operator gauges, process memory) pass
    the same lint and the recorder's row counters total correctly."""
    samples = _parse_samples(_metrics_lines(_recording_runtime()))
    rows_in = [v for f, _l, v in samples
               if f == "pathway_tpu_operator_rows_in"]
    rows_out = [v for f, _l, v in samples
                if f == "pathway_tpu_operator_rows_out"]
    assert rows_in == [10 * len(_STEP_SAMPLES_MS)]
    assert rows_out == [9 * len(_STEP_SAMPLES_MS)]


def test_exchange_plane_metrics_exposed_per_row():
    """A runtime with a cluster exports pathway_tpu_exchange_* with
    per-transport (tcp/shm) labels, including the per-row encode/decode
    gauges (the r5 encdec-regression surface), all passing the same
    exposition lint."""
    from pathway_tpu.engine.multiproc import Cluster

    rt = _FakeRuntime()
    cl = Cluster(2, 0, 41000)
    cl.stats.update({"rounds": 2, "shm_bytes_out": 90000,
                     "shm_bytes_in": 38000})
    cl.stats_by_transport["tcp"].update(
        {"encode_s": 0.010, "decode_s": 0.004,
         "rows_out": 2000, "rows_in": 1000,
         "bytes_out": 64000, "bytes_in": 32000, "messages": 4})
    cl.stats_by_transport["shm"].update(
        {"encode_s": 0.001, "decode_s": 0.002,
         "rows_out": 500, "rows_in": 1000,
         "bytes_out": 52, "bytes_in": 52, "messages": 4})
    rt.cluster = cl
    samples = _parse_samples(_metrics_lines(rt))
    by_series = {(f, labels.get("transport")): v
                 for f, labels, v in samples}
    assert by_series["pathway_tpu_exchange_encode_us_per_row", "tcp"] == \
        pytest.approx(5.0)
    assert by_series["pathway_tpu_exchange_decode_us_per_row", "tcp"] == \
        pytest.approx(4.0)
    assert by_series["pathway_tpu_exchange_decode_us_per_row", "shm"] == \
        pytest.approx(2.0)
    assert by_series["pathway_tpu_exchange_rows_out", "tcp"] == 2000
    assert by_series["pathway_tpu_exchange_rows_out", "shm"] == 500
    assert by_series["pathway_tpu_exchange_bytes_in", "tcp"] == 32000
    assert by_series["pathway_tpu_exchange_shm_bytes", None] == 128000
    assert by_series["pathway_tpu_exchange_rounds", None] == 2


def test_exchange_payload_row_counting():
    """payload_rows (and the codec's own row accounting) count genuine
    entry lists only: wm/bcast side-channels, scalars, liveness flags and
    plain lists are excluded — encode_us_per_row divides by rows moved,
    nothing else (the old _payload_rows counted any list it saw)."""
    from pathway_tpu.engine import wire
    from pathway_tpu.internals.keys import hash_values

    ents = [(hash_values("r", i), (f"w{i}", i), 1) for i in range(7)]
    payload = {"rows": {0: {3: ents}}, "wm": None, "bcast": {1: ents[:2]},
               "any": True, "closed": False}
    assert wire.payload_rows(payload) == 7
    chunks, _total, n_enc = wire.encode_frame(("x", 1, 0), payload)
    _tag, decoded, n_dec = wire.decode_frame(b"".join(chunks))
    assert n_enc == n_dec == 7
    assert decoded == payload
    assert wire.payload_rows({"any": True, "wm": 3}) == 0
    # a plain (non-entry) list is payload structure, not rows
    assert wire.payload_rows({"xs": [1, 2, 3]}) == 0
    # watermark side-channels never count, even when list-shaped
    assert wire.payload_rows({"wm": ents, "bcast": {0: ents}}) == 0


def test_paged_store_metrics_exposed():
    """A live paged pool surfaces the page-occupancy families (and they
    pass the exposition lint) plus the /status paged_store section."""
    import numpy as np

    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.ops.knn import BruteForceKnnIndex

    idx = BruteForceKnnIndex(8, tenant="acme")
    idx.add_batch([Pointer(i) for i in range(10)],
                  np.zeros((10, 8), np.float32))
    lines = _metrics_lines(_FakeRuntime())
    samples = {f: (labels, v) for f, labels, v in _parse_samples(lines)}
    assert samples["pathway_tpu_paged_pages_total"][1] >= 1
    assert "pathway_tpu_paged_occupancy_ratio" in samples
    assert samples["pathway_tpu_paged_grow_events"][1] >= 0
    tenant_rows = [(labels, v) for f, labels, v in _parse_samples(lines)
                   if f == "pathway_tpu_paged_tenant_pages"]
    assert any(labels.get("tenant") == "acme" for labels, _ in tenant_rows)
    server = MonitoringHttpServer(_FakeRuntime(), port=0)
    st = server.status_payload()
    assert st["paged_store"]["pages_total"] >= 1
    del idx  # release the pool so later exposition tests see a clean set


def test_trace_endpoint_serves_span_buffer():
    rt = _recording_runtime()
    rec = rt.scheduler.recorder
    rec.span("tick", 0.0, 0.004, ("tick", 0), rows=10, requests=0)
    rec.span("bridge.wait", 0.004, 0.005, ("tick", 0), depth=1)
    rec.span("bridge.leg", 0.005, 0.009, ("tick", 0))
    server = MonitoringHttpServer(rt, port=0)
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        payload = json.loads(urllib.request.urlopen(base + "/trace").read())
        assert payload["enabled"] is True
        assert len(payload["events"]) == len(_STEP_SAMPLES_MS)
        ev = payload["events"][-1]
        assert ev["operator"] == _AWKWARD
        assert ev["leg"] == "host"
        assert ev["rows_in"] == 10 and ev["rows_out"] == 9
        # the span store rides along, and a leg is one of its spans
        assert [sp["name"] for sp in payload["spans"]] == [
            "tick", "bridge.wait", "bridge.leg"]
        assert payload["spans"][0]["counts"] == {"rows": 10, "requests": 0}
        assert payload["device_legs"] == [
            {"tick": 0, "queue_wait_ms": 1.0, "exec_ms": 4.0}]
        # /status names the operator that dominated the last tick
        status = json.loads(
            urllib.request.urlopen(base + "/status").read())
        assert status["last_tick_dominator"]["operator"] == _AWKWARD
    finally:
        server.stop()


def test_trace_endpoint_without_recorder_reports_disabled():
    server = MonitoringHttpServer(_FakeRuntime(), port=0)
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        payload = json.loads(urllib.request.urlopen(base + "/trace").read())
        assert payload == {"enabled": False, "events": [],
                           "device_legs": [], "inflight": None}
    finally:
        server.stop()


def test_log_buffer_lines_env(monkeypatch):
    from pathway_tpu.internals.monitoring import _LogBuffer

    monkeypatch.setenv("PATHWAY_LOG_BUFFER_LINES", "3")
    assert _LogBuffer().records.maxlen == 3
    monkeypatch.setenv("PATHWAY_LOG_BUFFER_LINES", "bogus")
    assert _LogBuffer().records.maxlen == 8  # fallback, never a crash
    monkeypatch.delenv("PATHWAY_LOG_BUFFER_LINES")
    assert _LogBuffer().records.maxlen == 8


def test_engine_error_carries_user_trace():
    t = pw.debug.table_from_markdown(
        """
        a
        1
        0
        """
    )
    bad = t.flatten(t.a)  # flattening an int column: TypeError in-operator
    with pytest.raises(TypeError) as exc_info:  # original type preserved
        pw.debug.compute_and_print(bad)
    notes = "\n".join(getattr(exc_info.value, "__notes__", []))
    assert "in operator" in notes
    assert "test_monitoring_http.py" in notes
    assert "flatten" in notes


def test_persistence_watermark_metrics_exposed():
    """Commit-watermark durability families (PR 8): lag gauge, inflight
    at commit, commit counters, write retries, and the commit-wait
    histogram — all lint-clean with monotone cumulative buckets."""
    import pathway_tpu as pw
    from pathway_tpu.engine.http_server import MonitoringHttpServer
    from pathway_tpu.engine.persistence import PersistenceDriver
    from pathway_tpu.io._datasource import CallbackSource, Session

    rt = _FakeRuntime()
    backend = pw.persistence.Backend.mock()
    driver = PersistenceDriver(pw.persistence.Config.simple_config(backend))
    src = CallbackSource(lambda: iter(()), pw.schema_from_types(x=int))
    src.persistent_id = "m"
    rec = driver.attach_source(src, Session())
    rec.push("k", (1,), 1)
    driver.seal(4)
    driver.commit(6, watermark=4, inflight=3)
    rt.persistence = driver

    lines = _metrics_lines(rt)
    samples = {f: v for f, _l, v in _parse_samples(lines)}
    assert samples["pathway_tpu_commit_watermark"] == 4
    assert samples["pathway_tpu_commit_watermark_lag_ticks"] == 2
    assert samples["pathway_tpu_device_inflight_at_commit"] == 3
    assert samples["pathway_tpu_persistence_commits"] == 1
    assert samples["pathway_tpu_persistence_entries_committed"] == 1
    assert "pathway_tpu_persistence_write_retries" in samples
    assert samples["pathway_tpu_commit_wait_ms_count"] == 1
    # histogram: cumulative bucket counts are monotone and end at count
    buckets = [(l, v) for f, l, v in _parse_samples(lines)
               if f == "pathway_tpu_commit_wait_ms_bucket"]
    values = [v for _l, v in buckets]
    assert values == sorted(values)
    assert buckets[-1][0]["le"] == "+Inf"
    assert values[-1] == samples["pathway_tpu_commit_wait_ms_count"]
    # every family is TYPE-declared (same lint as the rest of the suite)
    typed = {l.split()[2] for l in lines if l.startswith("# TYPE")}
    for fam in ("pathway_tpu_commit_watermark_lag_ticks",
                "pathway_tpu_commit_wait_ms",
                "pathway_tpu_device_inflight_at_commit",
                "pathway_tpu_persistence_write_retries"):
        assert fam in typed
    # /status carries the same snapshot
    status = MonitoringHttpServer(rt, port=0).status_payload()
    assert status["persistence"]["watermark"] == 4
    assert status["persistence"]["lag_ticks"] == 2


def test_snapshot_tier_metrics_exposed():
    """Snapshot/compaction families (PR 10): age, bytes, generation,
    totals, compactions and the replayable-entry gauge — plus the
    /status.persistence naming of last snapshot tick + generation."""
    import pathway_tpu as pw
    from pathway_tpu.engine.http_server import MonitoringHttpServer
    from pathway_tpu.engine.persistence import PersistenceDriver
    from pathway_tpu.io._datasource import CallbackSource, Session

    rt = _FakeRuntime()
    backend = pw.persistence.Backend.mock()
    driver = PersistenceDriver(pw.persistence.Config.simple_config(backend))
    src = CallbackSource(lambda: iter(()), pw.schema_from_types(x=int))
    src.persistent_id = "m"
    rec = driver.attach_source(src, Session())
    rec.push("k", (1,), 1)
    driver.seal(2)
    driver.commit(2, watermark=2)
    assert driver.write_snapshot(2, {"nodes": {}}) is True
    rec.push("k2", (2,), 1)
    driver.seal(5)
    driver.commit(5, watermark=5)
    rt.persistence = driver

    lines = _metrics_lines(rt)
    samples = {f: v for f, _l, v in _parse_samples(lines)}
    assert samples["pathway_tpu_snapshot_age_ticks"] == 3  # tick 5 vs 2
    assert samples["pathway_tpu_snapshot_generation"] == 1
    assert samples["pathway_tpu_snapshots_total"] == 1
    assert samples["pathway_tpu_snapshot_bytes"] > 0
    assert samples["pathway_tpu_compactions_total"] == 1
    # compaction dropped the covered entry; one suffix entry remains
    assert samples["pathway_tpu_wal_replayable_entries"] == 1
    typed = {l.split()[2] for l in lines if l.startswith("# TYPE")}
    for fam in ("pathway_tpu_snapshot_age_ticks",
                "pathway_tpu_snapshot_bytes",
                "pathway_tpu_wal_replayable_entries",
                "pathway_tpu_compactions_total"):
        assert fam in typed
    status = MonitoringHttpServer(rt, port=0).status_payload()
    assert status["persistence"]["snapshot_tick"] == 2
    assert status["persistence"]["snapshot_generation"] == 1
    assert status["persistence"]["wal_replayable_entries"] == 1


# ---------------------------------------------------------------------------
# replica fleet exposition (PR 12): role fields + staleness families on the
# replica's own endpoint, and the router's /metrics — all through the same
# regex lint + TYPE-declaration contract as every other family
# ---------------------------------------------------------------------------

class _FakeTailer:
    """Duck-types engine/replica.ReplicaTailer's monitoring surface, with
    an awkward replica id to exercise label escaping."""

    replica_id = 'rep"lica\\one'
    applied_tick = 41
    primary_watermark = 44
    generation = 3

    def staleness_ticks(self):
        return 3

    def stats(self):
        return {
            "replica_id": self.replica_id,
            "applied_tick": self.applied_tick,
            "primary_watermark": self.primary_watermark,
            "staleness_ticks": self.staleness_ticks(),
            "generation": self.generation,
            "hydrate_wall_s": 0.125,
            "catchup_wall_s": 0.5,
            "records_applied": 7,
            "entries_applied": 70,
            "tailed_sources": ["vecs"],
        }


def test_replica_families_exposition_and_status_role():
    rt = _FakeRuntime()
    rt.role = "replica"
    rt.replica = _FakeTailer()
    lines = _metrics_lines(rt)
    by_family = {}
    for f, labels, v in _parse_samples(lines):
        by_family.setdefault(f, []).append((labels, v))
    typed = {l.split()[2] for l in lines if l.startswith("# TYPE")}
    for fam, want in (("pathway_tpu_replica_staleness_ticks", 3),
                      ("pathway_tpu_replica_applied_tick", 41),
                      ("pathway_tpu_replica_primary_watermark", 44),
                      ("pathway_tpu_replica_generation", 3),
                      ("pathway_tpu_replica_entries_applied", 70)):
        assert fam in typed, fam
        (labels, v), = by_family[fam]
        # the escaped label round-trips back to the raw replica id
        raw = labels["replica"].replace(r"\\", "\\").replace(r"\"", '"')
        assert raw == _FakeTailer.replica_id
        assert v == want, (fam, v)
    server = MonitoringHttpServer(rt, port=0)
    status = server.status_payload()
    assert status["role"] == "replica"
    assert status["applied_tick"] == 41
    assert status["staleness_ticks"] == 3
    assert status["replica"]["generation"] == 3
    healthy, hz = server.healthz_payload()
    assert hz["role"] == "replica"
    assert hz["applied_tick"] == 41 and hz["staleness_ticks"] == 3


def test_primary_role_default_on_status_and_healthz():
    server = MonitoringHttpServer(_FakeRuntime(), port=0)
    assert server.status_payload()["role"] == "primary"
    _healthy, hz = server.healthz_payload()
    assert hz["role"] == "primary" and hz["staleness_ticks"] == 0


def test_failover_families_exposition_and_status():
    """Failover families (PR 18): the fencing epoch gauge + fenced-write
    counter ride the persistence block; the promotion counter and
    failover wall-clock appear once this process has promoted — all
    through the exposition lint, and mirrored on /status."""
    import pathway_tpu as pw
    from pathway_tpu.engine.http_server import MonitoringHttpServer
    from pathway_tpu.engine.persistence import (FencedPrimaryError,
                                                PersistenceDriver)

    backend = pw.persistence.Backend.mock()
    cfg = pw.persistence.Config.simple_config(backend)
    promoted = PersistenceDriver(cfg)
    zombie = PersistenceDriver(cfg)
    assert promoted.claim_epoch("rescuer", min_epoch=3) == 3
    with pytest.raises(FencedPrimaryError):
        zombie.commit(1)

    # the promoted runtime: epoch gauge + promotion counter + wall-clock
    rt = _FakeRuntime()
    rt.persistence = promoted
    rt.role = "primary"
    rt.promotions = 1
    rt.promotion_tick = 9
    rt.failover_promotion_s = 1.25
    lines = _metrics_lines(rt)
    samples = {f: v for f, _l, v in _parse_samples(lines)}
    assert samples["pathway_tpu_fleet_epoch"] == 3
    assert samples["pathway_tpu_fenced_writes_total"] == 0
    assert samples["pathway_tpu_promotions_total"] == 1
    assert samples["pathway_tpu_failover_seconds"] == 1.25
    typed = {l.split()[2] for l in lines if l.startswith("# TYPE")}
    for fam in ("pathway_tpu_fleet_epoch", "pathway_tpu_fenced_writes_total",
                "pathway_tpu_promotions_total",
                "pathway_tpu_failover_seconds"):
        assert fam in typed, fam
    status = MonitoringHttpServer(rt, port=0).status_payload()
    assert status["promotions"] == 1
    assert status["promotion_tick"] == 9
    assert status["failover_promotion_s"] == 1.25

    # the fenced zombie: its counter is the split-brain smoking gun
    zrt = _FakeRuntime()
    zrt.persistence = zombie
    zsamples = {f: v for f, _l, v
                in _parse_samples(_metrics_lines(zrt))}
    assert zsamples["pathway_tpu_fenced_writes_total"] == 1
    assert "pathway_tpu_promotions_total" not in zsamples  # never promoted


def test_router_metrics_through_exposition_lint():
    """The router's /metrics body obeys the same exposition contract:
    every sample parses, every family is TYPE-declared, per-replica
    labels escape correctly."""
    import socket as _socket

    from pathway_tpu.engine.router import QueryRouter, ReplicaEndpoint

    router = QueryRouter(slo_ms=10.0)
    a, _b = _socket.socketpair()
    ep = ReplicaEndpoint('we"ird\\replica', "replica", "127.0.0.1", 1, a)
    ep.staleness_ticks = 5
    ep.applied_tick = 12
    for ms in (1.0, 2.0, 3.0, 40.0, 5.0, 6.0):
        ep.observe(ms)
    ep.requests = 6
    router._endpoints[ep.replica_id] = ep
    for ms in (5.0, 50.0):
        router._window.append(ms)
    lines = router.metrics_payload().splitlines()
    assert lines[-1] == "# EOF"
    typed = {l.split()[2] for l in lines if l.startswith("# TYPE")}
    seen = {}
    for f, labels, v in _parse_samples(lines):
        assert f in typed, f"router family {f!r} has no # TYPE line"
        seen.setdefault(f, []).append((labels, v))
    for fam in ("pathway_tpu_router_replicas",
                "pathway_tpu_router_requests_total",
                "pathway_tpu_router_failovers",
                "pathway_tpu_router_requests",
                "pathway_tpu_router_replica_p50_ms",
                "pathway_tpu_router_replica_p95_ms",
                "pathway_tpu_replica_staleness_ticks",
                "pathway_tpu_slo_burn_rate"):
        assert fam in seen, fam
    (labels, v), = seen["pathway_tpu_replica_staleness_ticks"]
    raw = labels["replica"].replace(r"\\", "\\").replace(r"\"", '"')
    assert raw == ep.replica_id and v == 5
    # p50 <= p95 (the exposed pair is ordered like the tracker's)
    p50 = seen["pathway_tpu_router_replica_p50_ms"][0][1]
    p95 = seen["pathway_tpu_router_replica_p95_ms"][0][1]
    assert p50 <= p95


# ---------------------------------------------------------------------------
# fleet metrics aggregation (engine/fleet_observability.py, PR 14): the
# /fleet/metrics merge must keep the SAME exposition contract the
# per-process endpoints are gated on — one TYPE line per family however
# many processes ship it, every sample re-labeled {process=,role=} with
# exposition-format escaping, and histogram aggregates that stay monotone
# ---------------------------------------------------------------------------

_ADVERSARIAL_PROCESS = 'pro"cess\\one\nx'
_ADVERSARIAL_REPLICA = 'rep"lica\\two'


def _fleet_doc(process: str, counters: dict[str, float],
               hist: tuple[tuple[float, int], ...] | None = None,
               role: str = "replica") -> tuple[dict, str]:
    lines = []
    for fam, v in counters.items():
        lines.append(f"# TYPE {fam} counter")
        lines.append(f"{fam} {v}")
    lines.append("# TYPE pathway_tpu_q_ms summary")
    lines.append('pathway_tpu_q_ms{quantile="0.5"} 4.0')
    lines.append("# TYPE pathway_tpu_up gauge")
    lines.append(
        f'pathway_tpu_up{{replica="{_ADVERSARIAL_REPLICA.replace(chr(92), chr(92) * 2).replace(chr(34), chr(92) + chr(34))}"}} 1')
    if hist is not None:
        lines.append("# TYPE pathway_tpu_wait_ms histogram")
        total = 0
        for le, c in hist:
            total = c
            le_s = "+Inf" if le == float("inf") else format(le, "g")
            lines.append(
                f'pathway_tpu_wait_ms_bucket{{le="{le_s}"}} {c}')
        lines.append(f"pathway_tpu_wait_ms_sum {float(total)}")
        lines.append(f"pathway_tpu_wait_ms_count {total}")
    lines.append("# EOF")
    return ({"process": process, "role": role}, "\n".join(lines) + "\n")


def test_fleet_metrics_label_escaping_round_trips():
    """Adversarial process AND replica ids survive the merge: the
    injected process label escapes per the exposition format and decodes
    back to the raw id, and pre-existing labels are untouched."""
    from pathway_tpu.engine.fleet_observability import merge_metrics

    merged = merge_metrics([
        _fleet_doc(_ADVERSARIAL_PROCESS, {"pathway_tpu_reqs": 3}),
        _fleet_doc("plain", {"pathway_tpu_reqs": 4}),
    ])
    samples = _parse_samples(merged.splitlines())
    procs = set()
    for f, labels, _v in samples:
        if f == "pathway_tpu_reqs" and "process" in labels:
            procs.add(labels["process"].replace(r"\\", "\x00")
                      .replace(r"\"", '"').replace(r"\n", "\n")
                      .replace("\x00", "\\"))
    assert _ADVERSARIAL_PROCESS in procs and "plain" in procs
    replicas = {labels["replica"].replace(r"\\", "\x00")
                .replace(r"\"", '"').replace("\x00", "\\")
                for f, labels, _v in samples
                if f == "pathway_tpu_up" and "replica" in labels}
    assert replicas == {_ADVERSARIAL_REPLICA}


def test_fleet_metrics_type_declared_once_per_family():
    """N processes shipping the same family must yield exactly ONE
    # TYPE declaration (Prometheus rejects redeclaration), with every
    per-process sample under it and every line lint-clean."""
    from pathway_tpu.engine.fleet_observability import merge_metrics

    docs = [_fleet_doc(f"p{i}", {"pathway_tpu_reqs": i})
            for i in range(4)]
    merged = merge_metrics(docs)
    lines = merged.splitlines()
    assert lines[-1] == "# EOF"
    type_lines = [l for l in lines if l.startswith("# TYPE")]
    families = [l.split()[2] for l in type_lines]
    assert len(families) == len(set(families)), families
    assert families.count("pathway_tpu_reqs") == 1
    samples = _parse_samples(lines)  # regex lint over every line
    reqs = [(labels.get("process"), v) for f, labels, v in samples
            if f == "pathway_tpu_reqs"]
    # 4 per-process samples + the _fleet sum
    assert len(reqs) == 5
    assert ("_fleet", 0 + 1 + 2 + 3) in reqs
    # every sample family is TYPE-declared (PR-5 contract)
    typed = set(families)
    for f, _labels, _v in samples:
        base = re.sub(r"_(bucket|sum|count)$", "", f)
        assert f in typed or base in typed, f


def test_fleet_metrics_histogram_merge_monotone():
    """Histogram families merge by summing cumulative buckets — the
    merged _fleet series must stay monotone with +Inf == _count, and the
    per-process pass-throughs keep their own invariants."""
    import math

    from pathway_tpu.engine.fleet_observability import merge_metrics

    h1 = ((1.0, 2), (5.0, 4), (float("inf"), 7))
    h2 = ((1.0, 1), (5.0, 5), (float("inf"), 6))
    merged = merge_metrics([
        _fleet_doc("p1", {}, hist=h1),
        _fleet_doc("p2", {}, hist=h2),
    ])
    samples = _parse_samples(merged.splitlines())
    fleet_buckets = []
    fleet_count = None
    for f, labels, v in samples:
        if labels.get("process") != "_fleet":
            continue
        if f == "pathway_tpu_wait_ms_bucket":
            le = math.inf if labels["le"] == "+Inf" else float(labels["le"])
            fleet_buckets.append((le, v))
        elif f == "pathway_tpu_wait_ms_count":
            fleet_count = v
    assert fleet_buckets, "no merged _fleet histogram emitted"
    fleet_buckets.sort(key=lambda b: b[0])
    counts = [c for _le, c in fleet_buckets]
    assert counts == sorted(counts), "merged buckets lost monotonicity"
    assert fleet_buckets[-1][0] == math.inf
    assert fleet_buckets[-1][1] == fleet_count == 7 + 6
    assert counts == [2 + 1, 4 + 5, 7 + 6]
    # summaries (quantiles) are pass-through only: no fake fleet p50
    assert not any(f == "pathway_tpu_q_ms"
                   and labels.get("process") == "_fleet"
                   for f, labels, _v in samples)
    # gauges pass through per-process only as well
    assert not any(f == "pathway_tpu_up"
                   and labels.get("process") == "_fleet"
                   for f, labels, _v in samples)


def test_fleet_metrics_family_named_like_histogram_suffix():
    """A counter literally NAMED *_count (or *_sum/_bucket) must keep
    its own TYPE line and _fleet aggregate — the histogram sub-sample
    resolution only applies to UNDECLARED suffixed samples."""
    from pathway_tpu.engine.fleet_observability import merge_metrics

    doc = ("# TYPE pathway_tpu_foo_count counter\n"
           "pathway_tpu_foo_count 5\n# EOF\n")
    merged = merge_metrics([({"process": "p1", "role": "replica"}, doc),
                            ({"process": "p2", "role": "replica"}, doc)])
    lines = merged.splitlines()
    assert lines.count("# TYPE pathway_tpu_foo_count counter") == 1
    samples = _parse_samples(lines)
    vals = {labels.get("process"): v for f, labels, v in samples
            if f == "pathway_tpu_foo_count"}
    assert vals == {"p1": 5, "p2": 5, "_fleet": 10}


def test_trace_endpoint_chrome_format_carries_fleet_meta():
    """/trace?format=chrome serves the mergeable payload: traceEvents +
    pathway_meta (pid, role, process, clock anchor)."""
    rt = _recording_runtime()
    server = MonitoringHttpServer(rt, port=0)
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        payload = json.loads(urllib.request.urlopen(
            base + "/trace?format=chrome").read())
        assert isinstance(payload["traceEvents"], list)
        meta = payload["pathway_meta"]
        assert meta["pid"] > 0 and meta["role"] and meta["process"]
        assert meta["epoch_wall_us"] > 0
        # the plain /trace contract is unchanged
        plain = json.loads(urllib.request.urlopen(
            base + "/trace").read())
        assert plain["enabled"] is True and "events" in plain
    finally:
        server.stop()


def test_router_fleet_metrics_endpoint_merges_live_scrape():
    """The router's /fleet/metrics scrapes a REAL monitoring endpoint
    (announced via heartbeat monitoring_port) and serves the merged
    document with the router's own families alongside."""
    import socket as _socket

    from pathway_tpu.engine.router import QueryRouter, ReplicaEndpoint

    server = MonitoringHttpServer(_recording_runtime(), port=0)
    server.start()
    router = QueryRouter(port=0, control_port=0)
    router.start()
    try:
        a, _b = _socket.socketpair()
        ep = ReplicaEndpoint("r1", "replica", "127.0.0.1", 1, a)
        ep.monitoring_port = server.port
        router._endpoints["r1"] = ep
        merged = urllib.request.urlopen(
            f"http://127.0.0.1:{router.port}/fleet/metrics",
            timeout=10).read().decode()
        lines = merged.splitlines()
        assert lines[-1] == "# EOF"
        samples = _parse_samples(lines)
        procs = {labels.get("process") for _f, labels, _v in samples}
        assert {"router", "r1"} <= procs
        # a per-process family from the scraped endpoint rode through,
        # re-labeled
        assert any(f == "pathway_tpu_insertions"
                   and labels.get("process") == "r1"
                   and labels.get("role") == "replica"
                   for f, labels, _v in samples)
        # one TYPE line per family in the merged doc
        fams = [l.split()[2] for l in lines if l.startswith("# TYPE")]
        assert len(fams) == len(set(fams))
    finally:
        router.stop()
        server.stop()


# ---------------------------------------------------------------------------
# auto-jit tier exposition (internals/autojit.py): counter families under
# the same regex lint + TYPE-declaration contract, /status tier state
# ---------------------------------------------------------------------------

def test_autojit_families_exposed_and_status_tier_state(monkeypatch):
    from pathway_tpu.internals import autojit

    monkeypatch.setenv("PATHWAY_AUTO_JIT", "1")
    autojit.reset_stats()
    autojit._bump("programs")
    autojit._bump("compiles", 3)
    autojit._bump("demotions")
    autojit._bump("device_dispatches", 7)
    try:
        lines = _metrics_lines(_FakeRuntime())
        typed = {l.split()[2] for l in lines if l.startswith("# TYPE")}
        seen = {f: v for f, _labels, v in _parse_samples(lines)}
        for fam, want in (("pathway_tpu_autojit_enabled", 1),
                          ("pathway_tpu_autojit_programs", 1),
                          ("pathway_tpu_autojit_compiles", 3),
                          ("pathway_tpu_autojit_demotions", 1),
                          ("pathway_tpu_autojit_device_dispatches", 7),
                          ("pathway_tpu_autojit_vector_dispatches", 0),
                          ("pathway_tpu_autojit_fallback_batches", 0)):
            assert fam in typed, fam
            assert seen[fam] == want, (fam, seen[fam])
        # /status names the tier state (enabled flag + backend mix)
        status = MonitoringHttpServer(_FakeRuntime(), port=0).status_payload()
        assert status["autojit"]["enabled"] is True
        assert status["autojit"]["programs"] == 1
        assert "live_programs" in status["autojit"]
        # the escape hatch is visible on both surfaces
        monkeypatch.setenv("PATHWAY_AUTO_JIT", "0")
        lines = _metrics_lines(_FakeRuntime())
        seen = {f: v for f, _labels, v in _parse_samples(lines)}
        assert seen["pathway_tpu_autojit_enabled"] == 0
        status = MonitoringHttpServer(_FakeRuntime(), port=0).status_payload()
        assert status["autojit"]["enabled"] is False
    finally:
        autojit.reset_stats()


# ---------------------------------------------------------------------------
# unified 503 contract (engine/qos.py): every 503 — webserver shed,
# router unroutable / fleet-dead, proxied shed — echoes
# X-Pathway-Request-Id AND carries Retry-After
# ---------------------------------------------------------------------------

def _drain_http_error(ei):
    err = ei.value
    err.read()
    return err


def test_router_unroutable_503_carries_id_and_retry_after():
    import urllib.error

    from pathway_tpu.engine.router import QueryRouter

    router = QueryRouter()
    router.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{router.port}/q", data=b"{}",
            method="POST",
            headers={"X-Pathway-Request-Id": "client-rid-42"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        err = _drain_http_error(ei)
        assert err.code == 503
        # the id the client holds rides the 503 back (fleet grep-ability)
        assert err.headers["X-Pathway-Request-Id"] == "client-rid-42"
        assert int(err.headers["Retry-After"]) >= 1
        assert router.unroutable_total == 1
    finally:
        router.stop()


def test_router_propagates_upstream_retry_after_on_shed_503():
    """A backend's QoS gate shed the query: the router's proxy must keep
    the upstream Retry-After (previously only body+content-type crossed
    the proxy) and still echo the request id."""
    import socket
    import threading
    import urllib.error
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from pathway_tpu.engine.router import QueryRouter, ReplicaEndpoint

    class _SheddingBackend(BaseHTTPRequestHandler):
        def do_POST(self):
            self.rfile.read(int(self.headers.get("Content-Length") or 0))
            body = b"query shed: admission queue full"
            self.send_response(503)
            self.send_header("Retry-After", "7")
            self.send_header("Content-Type", "text/plain")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):
            pass

    backend = ThreadingHTTPServer(("127.0.0.1", 0), _SheddingBackend)
    bthread = threading.Thread(target=backend.serve_forever, daemon=True)
    bthread.start()
    router = QueryRouter()
    router.start()
    try:
        a, b = socket.socketpair()
        ep = ReplicaEndpoint("shedder", "replica", "127.0.0.1",
                             backend.server_address[1], a)
        router._endpoints["shedder"] = ep
        req = urllib.request.Request(
            f"http://127.0.0.1:{router.port}/q", data=b"{}",
            method="POST",
            headers={"X-Pathway-Request-Id": "rid-shed-1"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        err = _drain_http_error(ei)
        assert err.code == 503
        assert err.headers["Retry-After"] == "7"       # propagated
        assert err.headers["X-Pathway-Request-Id"] == "rid-shed-1"
        b.close()
    finally:
        router.stop()
        backend.shutdown()
        backend.server_close()


def test_webserver_shed_503_carries_id_and_retry_after():
    """The webserver's own shed path (QueryShedError out of a handler)
    emits the same 503 pair — id echo + Retry-After."""
    import urllib.error

    from pathway_tpu.engine.qos import QueryShedError
    from pathway_tpu.io.http import PathwayWebserver

    ws = PathwayWebserver(host="127.0.0.1", port=0)

    async def shedding_handler(payload):
        raise QueryShedError("admission queue full (test)", 3)

    ws.register("/shed", ("POST",), shedding_handler, None)
    ws.start()
    req = urllib.request.Request(
        f"http://127.0.0.1:{ws.port}/shed", data=b"{}", method="POST",
        headers={"X-Pathway-Request-Id": "rid-web-9"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=10)
    err = _drain_http_error(ei)
    assert err.code == 503
    assert err.headers["X-Pathway-Request-Id"] == "rid-web-9"
    assert err.headers["Retry-After"] == "3"


# ---------------------------------------------------------------------------
# semantic result cache exposition (engine/result_cache.py)
# ---------------------------------------------------------------------------

def test_result_cache_metrics_exposed():
    """A live result cache surfaces the pathway_tpu_cache_* families
    (passing the exposition lint ridden by _parse_samples) plus the
    /status result_cache section."""
    import numpy as np

    from pathway_tpu.ops.knn import BruteForceKnnIndex

    idx = BruteForceKnnIndex(4, reserved_space=16)
    assert idx.result_cache is not None
    idx.add_batch([i for i in range(4)], np.eye(4, dtype=np.float32))
    q = np.ones(4, np.float32)
    idx.search([(0, q, 2, None)])
    fp = b"\x00" * 16
    idx.result_cache.fill(fp, ((1, 0.5),), frozenset({0}), 0.5, q)
    assert idx.result_cache.lookup(fp) is not None      # one hit
    idx.result_cache.lookup(b"\x01" * 16)               # one miss
    lines = _metrics_lines(_FakeRuntime())
    typed = {l.split()[2] for l in lines if l.startswith("# TYPE")}
    samples = {f: (labels, v) for f, labels, v in _parse_samples(lines)}
    for fam in ("pathway_tpu_cache_hits", "pathway_tpu_cache_misses",
                "pathway_tpu_cache_invalidations",
                "pathway_tpu_cache_entries", "pathway_tpu_cache_hit_ratio",
                "pathway_tpu_cache_evictions",
                "pathway_tpu_cache_index_version",
                "pathway_tpu_cache_invalidations_per_tick"):
        assert fam in samples, fam
        assert fam in typed, f"{fam} has no # TYPE line"
    assert samples["pathway_tpu_cache_hits"][1] >= 1
    assert samples["pathway_tpu_cache_misses"][1] >= 1
    assert 0.0 <= samples["pathway_tpu_cache_hit_ratio"][1] <= 1.0
    server = MonitoringHttpServer(_FakeRuntime(), port=0)
    st = server.status_payload()
    assert st["result_cache"]["entries"] >= 1
    assert st["result_cache"]["hits"] >= 1
    del idx  # release the live cache so later exposition tests are clean


def test_router_cache_metrics_and_status():
    """The router's fleet-cache families ride its /metrics body under
    the same exposition contract, and /status carries result_cache with
    the configured routes + watermark liveness."""
    import socket as _socket

    from pathway_tpu.engine.router import QueryRouter, ReplicaEndpoint
    from pathway_tpu.engine.result_cache import RouterResultCache

    router = QueryRouter(cache_routes=("/query",))
    a, _b = _socket.socketpair()
    ep = ReplicaEndpoint("r0", "replica", "127.0.0.1", 1, a)
    ep.index_version = 7
    router._endpoints[ep.replica_id] = ep
    wm = router._fleet_watermark()
    assert wm == frozenset({("r0", 7)})
    key = RouterResultCache.key("POST", "/query", b"{}")
    router.response_cache.fill(key, wm, 200, b"ok", "application/json")
    assert router.response_cache.lookup(key, wm) is not None
    lines = router.metrics_payload().splitlines()
    typed = {l.split()[2] for l in lines if l.startswith("# TYPE")}
    seen = {}
    for f, labels, v in _parse_samples(lines):
        assert f in typed, f"router family {f!r} has no # TYPE line"
        seen.setdefault(f, []).append((labels, v))
    for fam in ("pathway_tpu_router_cache_hits",
                "pathway_tpu_router_cache_misses",
                "pathway_tpu_router_cache_invalidations",
                "pathway_tpu_router_cache_entries",
                "pathway_tpu_router_cache_hit_ratio",
                "pathway_tpu_replica_index_version"):
        assert fam in seen, fam
    assert seen["pathway_tpu_router_cache_hits"][0][1] >= 1
    assert seen["pathway_tpu_router_cache_entries"][0][1] == 1
    (labels, v), = seen["pathway_tpu_replica_index_version"]
    assert labels["replica"] == "r0" and v == 7
    st = router.status_payload()
    assert st["result_cache"]["routes"] == ["/query"]
    assert st["result_cache"]["watermark_live"] is True
    assert st["result_cache"]["entries"] == 1


# ---------------------------------------------------------------------------
# continuous profiling plane (engine/profiler.py): exposition + endpoints
# ---------------------------------------------------------------------------

@pytest.fixture
def _installed_profiler():
    """A live profiler with known device dispatches and folded stacks
    (sampler not started — the endpoints read state, not the thread)."""
    from pathway_tpu.engine.profiler import (Profiler, install_profiler,
                                             knn_search_cost, machine_params)

    # rated against the v5e row by hand — the suite's own device (cpu)
    # has no row, and an unrated profiler exports no utilization gauges
    prof = Profiler(sample_interval_ms=1e6,
                    machine=machine_params("TPU v5 lite"))
    f, b = knn_search_cost(4, 1024, 64)
    prof.record_dispatch("knn_search", f, b, 2.0)
    prof.record_dispatch("encoder_forward", 1e9, 1e6, 5.0)
    with prof._lock:
        prof._stacks[("worker", ("run (graph.py:10)", "step (knn.py:20)"))] = 3
        prof._stacks[("device-bridge", ("work (bridge.py:5)",
                                        "[device:knn_q]"))] = 2
        prof.samples_total = 5
        prof.device_attributed_samples = 2
    install_profiler(prof)
    yield prof
    install_profiler(None)


_PROFILER_FAMILIES = (
    "pathway_tpu_mfu_rolling", "pathway_tpu_hbm_bw_util",
    "pathway_tpu_kernel_device_ms", "pathway_tpu_kernel_dispatches",
    "pathway_tpu_kernel_mfu", "pathway_tpu_kernel_arithmetic_intensity",
    "pathway_tpu_profiler_samples",
    "pathway_tpu_profiler_device_attributed_samples",
    "pathway_tpu_profiler_overhead_ratio",
    "pathway_tpu_profiler_distinct_stacks",
)


def test_profiler_families_exposition_and_status(_installed_profiler):
    lines = _metrics_lines(_recording_runtime())
    samples = _parse_samples(lines)  # regex lint over every line
    fam = {f for f, _l, _v in samples}
    typed = {l.split()[2] for l in lines if l.startswith("# TYPE")}
    for name in _PROFILER_FAMILIES:
        assert name in fam, f"{name} not exported"
        assert name in typed, f"{name} has no # TYPE declaration"
    kernels = {labels["family"]: v for f, labels, v in samples
               if f == "pathway_tpu_kernel_device_ms"}
    assert kernels == {"knn_search": 2.0, "encoder_forward": 5.0}
    counts = {f: v for f, labels, v in samples if not labels}
    assert counts["pathway_tpu_profiler_samples"] == 5.0
    assert counts["pathway_tpu_profiler_device_attributed_samples"] == 2.0
    assert counts["pathway_tpu_mfu_rolling"] > 0.0
    # /status.profiler: roofline verdict per family
    server = MonitoringHttpServer(_recording_runtime(), port=0)
    status = server.status_payload()
    rooflines = {fam: st["roofline"]["bound_by"]
                 for fam, st in status["profiler"]["families"].items()}
    assert rooflines["knn_search"] == "bandwidth"
    assert status["profiler"]["host"]["samples_total"] == 5


def test_metrics_without_profiler_omit_the_families():
    lines = _metrics_lines(_recording_runtime())
    fam = {f for f, _l, _v in _parse_samples(lines)}
    assert not fam & set(_PROFILER_FAMILIES)


def _tenant_runtime():
    """A recording runtime whose tracker completed per-tenant queries:
    acme fast (inside the 50ms SLO), bigco slow (burning budget)."""
    import time as _time

    from pathway_tpu.engine.request_tracker import RequestTracker

    rt = _recording_runtime()
    tr = RequestTracker(slo_ms=50.0)
    for tenant, ms, n in (("acme", 10.0, 8), ("bigco", 120.0, 8)):
        for i in range(n):
            base = _time.perf_counter()
            span = tr.start(f"{tenant}{i}", "/q", t_ingress=base)
            span.key = (tenant, i)
            tr._by_key[span.key] = span
            span.t_enqueued = base
            tr.attribute_tenant([span.key], tenant)
            span.t_resolved = base + ms / 1e3
            tr.finish(span)
    rt.scheduler.recorder.requests = tr
    return rt


def test_tenant_serving_families_exposition():
    rt = _tenant_runtime()
    lines = _metrics_lines(rt)
    samples = _parse_samples(lines)  # regex lint over every line
    # tenant-labeled quantiles ride under the EXISTING summary family —
    # exactly one TYPE declaration for it
    type_lines = [l.split()[2] for l in lines if l.startswith("# TYPE")]
    assert type_lines.count("pathway_tpu_query_e2e_latency_ms") == 1
    q = {(labels["tenant"], labels["quantile"]): v
         for f, labels, v in samples
         if f == "pathway_tpu_query_e2e_latency_ms" and "tenant" in labels}
    assert set(q) == {("acme", "0.5"), ("acme", "0.95"),
                      ("bigco", "0.5"), ("bigco", "0.95")}
    assert q[("acme", "0.5")] <= q[("acme", "0.95")]
    assert q[("bigco", "0.5")] > q[("acme", "0.95")]
    counts = {labels["tenant"]: v for f, labels, v in samples
              if f == "pathway_tpu_query_e2e_latency_ms_count"
              and "tenant" in labels}
    assert counts == {"acme": 8.0, "bigco": 8.0}
    burn = {labels["tenant"]: v for f, labels, v in samples
            if f == "pathway_tpu_tenant_slo_burn_rate"}
    assert burn["acme"] == 0.0
    assert burn["bigco"] > 1.0
    assert "pathway_tpu_tenant_slo_burn_rate" in type_lines


def test_profile_host_endpoint_serves_collapsed_stacks(_installed_profiler):
    server = MonitoringHttpServer(_recording_runtime(), port=0)
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        resp = urllib.request.urlopen(base + "/profile/host")
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()
        lines = text.strip().splitlines()
        line_re = re.compile(r"^[^; ][^;]*(;[^;]+)* \d+$")
        for ln in lines:
            assert line_re.match(ln), f"bad collapsed line: {ln!r}"
        assert "worker;run (graph.py:10);step (knn.py:20) 3" in lines
        # the in-flight tag survives as the synthetic leaf frame
        assert any(ln.endswith("[device:knn_q] 2") for ln in lines)
        # ?seconds=N serves only the window's delta (no new samples
        # arrive while the sampler is idle -> empty window)
        resp = urllib.request.urlopen(base + "/profile/host?seconds=0.05")
        assert resp.read().decode() == ""
    finally:
        server.stop()


def test_profile_endpoints_503_without_profiler():
    import urllib.error

    server = MonitoringHttpServer(_recording_runtime(), port=0)
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        for path in ("/profile/host", "/profile/device/start",
                     "/profile/device/stop"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + path)
            err = _drain_http_error(ei)
            assert err.code == 503
    finally:
        server.stop()


def test_profile_device_capture_contract(_installed_profiler, monkeypatch,
                                         tmp_path):
    """start -> artifact dir in JSON; double-start 409; stop returns the
    same dir; idle stop 409. jax.profiler is stubbed: the test pins OUR
    endpoint contract, not XLA's tracer."""
    import urllib.error

    import jax

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    server = MonitoringHttpServer(_recording_runtime(), port=0)
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        target = str(tmp_path / "cap")
        from urllib.parse import quote

        out = json.loads(urllib.request.urlopen(
            base + f"/profile/device/start?dir={quote(target, safe='')}"
        ).read())
        assert out == {"capturing": True, "dir": target}
        import os

        assert os.path.isdir(target)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/profile/device/start")
        assert _drain_http_error(ei).code == 409  # one capture at a time
        out = json.loads(urllib.request.urlopen(
            base + "/profile/device/stop").read())
        assert out == {"capturing": False, "dir": target}
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/profile/device/stop")
        assert _drain_http_error(ei).code == 409  # nothing running
        assert _installed_profiler.captures_total == 1
    finally:
        server.stop()


def test_fleet_merge_relabels_profiler_gauges_per_process():
    """PR-14 /fleet/metrics: the profiler gauges ride the merged
    exposition with {process=,role=} labels and ONE TYPE declaration —
    per-role MFU is readable straight off the fleet scrape."""
    from pathway_tpu.engine.fleet_observability import merge_metrics

    def doc(process, role, mfu, knn_ms):
        lines = [
            "# TYPE pathway_tpu_mfu_rolling gauge",
            f"pathway_tpu_mfu_rolling {mfu}",
            "# TYPE pathway_tpu_kernel_device_ms counter",
            f'pathway_tpu_kernel_device_ms{{family="knn_search"}} {knn_ms}',
            "# EOF",
        ]
        return ({"process": process, "role": role},
                "\n".join(lines) + "\n")

    merged = merge_metrics([doc("primary-0", "primary", 0.31, 12.0),
                            doc("replica-1", "replica", 0.07, 48.0)])
    lines = merged.splitlines()
    samples = _parse_samples(lines)  # regex lint over every line
    type_lines = [l.split()[2] for l in lines if l.startswith("# TYPE")]
    assert type_lines.count("pathway_tpu_mfu_rolling") == 1
    assert type_lines.count("pathway_tpu_kernel_device_ms") == 1
    mfu = {(labels.get("process"), labels.get("role")): v
           for f, labels, v in samples if f == "pathway_tpu_mfu_rolling"
           if "process" in labels}
    assert mfu[("primary-0", "primary")] == 0.31
    assert mfu[("replica-1", "replica")] == 0.07
    knn = {labels.get("process"): (v, labels.get("family"))
           for f, labels, v in samples
           if f == "pathway_tpu_kernel_device_ms" and "process" in labels}
    assert knn["primary-0"] == (12.0, "knn_search")
    assert knn["replica-1"] == (48.0, "knn_search")
