"""Fleet observability plane (engine/fleet_observability.py, PR 14):
cross-process request-id propagation, the clock-aligned trace merge,
the router's fleet surfaces, the perf-trajectory regression watch, and
the atomic-write directory-fsync durability fix."""

from __future__ import annotations

import http.client
import http.server
import json
import os
import socket
import threading
import time
import urllib.request

import pytest

from pathway_tpu.engine import fleet_observability as fo
from pathway_tpu.engine.flight_recorder import atomic_write_json
from pathway_tpu.testing import faults


# ---------------------------------------------------------------------------
# request-id propagation
# ---------------------------------------------------------------------------

def test_adopt_request_id_sanitizes_and_adopts():
    from pathway_tpu.io.http import _adopt_request_id

    assert _adopt_request_id("rtr-1a2b-000007") == "rtr-1a2b-000007"
    assert _adopt_request_id("a.b:c_d-e") == "a.b:c_d-e"
    # junk must not leak into traces/labels: minted instead
    for bad in (None, "", "   ", "has space", 'quo"te', "new\nline",
                "x" * 200):
        rid = _adopt_request_id(bad)
        assert rid != bad and "-" in rid


def test_webserver_adopts_inbound_request_id():
    """The serving process adopts the router's id instead of minting its
    own — the contract that makes ONE id name a query end to end."""
    from pathway_tpu.io.http import PathwayWebserver

    ws = PathwayWebserver(host="127.0.0.1", port=0)

    async def handler(payload):
        return {"ok": True}

    ws.register("/echo", ("POST",), handler, None)
    ws.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{ws.port}/echo", data=b"{}",
            headers={"Content-Type": "application/json",
                     "X-Pathway-Request-Id": "rtr-ffff-000042"})
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.headers["X-Pathway-Request-Id"] == \
                "rtr-ffff-000042"
        # an unsafe inbound id is replaced, and the replacement is echoed
        req = urllib.request.Request(
            f"http://127.0.0.1:{ws.port}/echo", data=b"{}",
            headers={"Content-Type": "application/json",
                     "X-Pathway-Request-Id": 'bad id with "junk"'})
        with urllib.request.urlopen(req, timeout=10) as resp:
            rid = resp.headers["X-Pathway-Request-Id"]
            assert rid and rid != 'bad id with "junk"'
    finally:
        pass  # webserver threads are daemonic; no teardown surface


def _make_router(**kw):
    from pathway_tpu.engine.router import QueryRouter

    router = QueryRouter(port=0, control_port=0, **kw)
    router.start()
    return router


def _post(port: int, path: str, headers: dict,
          body: bytes = b"{}") -> http.client.HTTPResponse:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("POST", path, body=body,
                 headers={"Content-Type": "application/json", **headers})
    return conn.getresponse()


def test_router_echoes_request_id_on_503():
    """Satellite pin: an unroutable query's 503 still carries the id the
    client sent — a lost query stays greppable fleet-wide."""
    router = _make_router()
    try:
        resp = _post(router.port, "/q",
                     {"X-Pathway-Request-Id": "rtr-dead-000001"})
        body = resp.read()
        assert resp.status == 503, body
        assert resp.headers["X-Pathway-Request-Id"] == "rtr-dead-000001"
        # a query that arrived without an id gets one minted AT the
        # router and echoed, even on the 503
        resp = _post(router.port, "/q", {})
        resp.read()
        assert resp.status == 503
        assert resp.headers["X-Pathway-Request-Id"].startswith("rtr-")
    finally:
        router.stop()


class _CaptureBackend:
    """A one-route HTTP backend that records every request's headers."""

    def __init__(self):
        outer = self
        self.seen: list[dict] = []

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                outer.seen.append(dict(self.headers))
                length = int(self.headers.get("Content-Length") or 0)
                self.rfile.read(length)
                body = b'{"ok": true}'
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):
                pass

        self.httpd = http.server.HTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.httpd.server_address[1]
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def _endpoint(router, rid: str, host: str, port: int):
    from pathway_tpu.engine.router import ReplicaEndpoint

    a, _b = socket.socketpair()
    ep = ReplicaEndpoint(rid, "replica", host, port, a)
    router._endpoints[rid] = ep
    return ep


def test_router_failover_replay_carries_same_id_and_hop():
    """Satellite pin: the failover replay forwards the SAME request id
    (plus the hop counter) to the rescuing replica, the response echoes
    it, and the router-side span records forward(fail) + failover(ok)."""
    backend = _CaptureBackend()
    # a dead endpoint: a port bound and never listened on -> connection
    # refused, and held to the end so that no other socket (the router's
    # own, another worker's) is handed the same number meanwhile
    dead = socket.socket()
    dead.bind(("127.0.0.1", 0))
    dead_port = dead.getsockname()[1]
    router = _make_router()
    try:
        _endpoint(router, "r-dead", "127.0.0.1", dead_port)
        _endpoint(router, "r-live", "127.0.0.1", backend.port)
        resp = _post(router.port, "/q",
                     {"X-Pathway-Request-Id": "rtr-abcd-000009"})
        data = resp.read()
        assert resp.status == 200, data
        assert resp.headers["X-Pathway-Request-Id"] == "rtr-abcd-000009"
        assert resp.headers["X-Pathway-Failovers"] == "1"
        assert resp.headers["X-Pathway-Replica"] == "r-live"
        # the rescuing replica received the SAME id with hop 0 -> 1
        assert len(backend.seen) == 1
        seen = backend.seen[0]
        assert seen["X-Pathway-Request-Id"] == "rtr-abcd-000009"
        assert seen["X-Pathway-Hop"] == "1"
        # router-side span: route + failed forward + rescuing failover
        spans = list(router.request_log.completed)
        assert len(spans) == 1
        span = spans[0]
        assert span.rid == "rtr-abcd-000009"
        assert span.replica == "r-live" and span.failovers() == 1
        stages = [(s, r, ok) for s, r, _t0, _t1, ok in span.attempts]
        assert stages == [("forward", "r-dead", False),
                          ("failover", "r-live", True)]
    finally:
        router.stop()
        backend.stop()
        dead.close()


def test_router_p50_skew_metric_exposed():
    """Satellite: router-observed vs replica-self-reported p50 skew is a
    per-replica gauge — a clock-drifted or overloaded replica shows up
    before it breaches SLO."""
    router = _make_router()
    try:
        ep = _endpoint(router, "r1", "127.0.0.1", 1)
        for ms in (10.0, 10.0, 10.0, 10.0, 10.0, 10.0):
            ep.observe(ms)
        ep.reported_p50_ms = 4.0
        assert ep.p50_skew_ms() == pytest.approx(6.0)
        metrics = router.metrics_payload()
        assert ('pathway_tpu_router_replica_p50_skew_ms{replica="r1"} '
                "6.0") in metrics
        assert "# TYPE pathway_tpu_router_replica_p50_skew_ms gauge" \
            in metrics
        # without a self-report there is no skew sample (absent, not 0)
        ep.reported_p50_ms = None
        assert "p50_skew_ms" not in router.metrics_payload().replace(
            "# TYPE pathway_tpu_router_replica_p50_skew_ms gauge", "")
    finally:
        router.stop()


def test_fleet_status_one_json(monkeypatch):
    router = _make_router()
    try:
        ep = _endpoint(router, "r1", "127.0.0.1", 1)
        ep.apply_heartbeat({"applied_tick": 41, "staleness_ticks": 3,
                            "generation": 2, "burn_rate": 0.25,
                            "p50_ms": 4.0})
        st = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{router.port}/fleet/status",
            timeout=10).read())
        assert st["role"] == "router"
        assert "burn_rate" in st
        assert set(st["request_stages"]) == {"route", "forward",
                                             "failover"}
        (member,) = st["fleet"]
        assert member["replica"] == "r1"
        assert member["applied_tick"] == 41
        assert member["staleness_ticks"] == 3
        assert member["burn_rate"] == 0.25
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# trace merge
# ---------------------------------------------------------------------------

def _router_payload(rid="abc", epoch_wall_us=1_000_000.0):
    return {
        "traceEvents": [
            {"ph": "M", "pid": 0, "tid": 0, "name": "thread_name",
             "args": {"name": "router requests"}},
            {"ph": "b", "cat": "router_request", "id": f"req-{rid}",
             "pid": 0, "tid": 0, "ts": 500_000.0, "name": f"req {rid}",
             "args": {"request_id": rid, "failovers": 1}},
            {"ph": "e", "cat": "router_request", "id": f"req-{rid}",
             "pid": 0, "tid": 0, "ts": 700_000.0, "name": f"req {rid}"},
        ],
        "displayTimeUnit": "ms",
        "pathway_meta": {"pid": 101, "process": "router",
                         "role": "router",
                         "epoch_wall_us": epoch_wall_us},
    }


def _serving_payload(rid="abc", process="r2", epoch_wall_us=2_000_000.0):
    return {
        "traceEvents": [
            {"ph": "b", "cat": "request", "id": f"req-{rid}", "pid": 0,
             "tid": 2, "ts": 0.0, "name": f"req {rid}",
             "args": {"request_id": rid}},
            {"ph": "e", "cat": "request", "id": f"req-{rid}", "pid": 0,
             "tid": 2, "ts": 90_000.0, "name": f"req {rid}"},
        ],
        "displayTimeUnit": "ms",
        "pathway_meta": {"pid": 202, "process": process,
                         "role": "replica",
                         "epoch_wall_us": epoch_wall_us},
    }


def test_merge_traces_aligns_clocks_and_links_processes():
    merged = fo.merge_traces([_router_payload(), _serving_payload()])
    events = merged["traceEvents"]
    fleet = merged["pathway_fleet"]
    assert [p["role"] for p in fleet["processes"]] == ["router",
                                                       "replica"]
    assert fleet["cross_process_request_ids"] == ["abc"]
    # distinct merged pids, named process tracks
    names = {e["pid"]: e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert set(names.values()) == {"router:router", "replica:r2"}
    # clock alignment: origin is the earliest epoch (router, 1.0s); the
    # serving process's ts shift by the 1.0s epoch difference
    router_b = next(e for e in events
                    if e.get("cat") == "router_request"
                    and e["ph"] == "b")
    serving_b = next(e for e in events if e.get("cat") == "request"
                     and e["ph"] == "b")
    assert router_b["ts"] == pytest.approx(500_000.0)
    assert serving_b["ts"] == pytest.approx(1_000_000.0)
    assert router_b["pid"] != serving_b["pid"]
    # the cross-process flow arrow: s on the router's span, f on the
    # serving (rescuing) process's span
    s = next(e for e in events if e["ph"] == "s" and e["cat"] == "fleet")
    f = next(e for e in events if e["ph"] == "f" and e["cat"] == "fleet")
    assert s["id"] == f["id"] == "xreq-abc"
    assert s["pid"] == router_b["pid"]
    assert f["pid"] == serving_b["pid"]
    assert s["ts"] == pytest.approx(router_b["ts"])


def test_merge_traces_tolerates_missing_meta_and_empty():
    empty = fo.merge_traces([])
    assert empty["traceEvents"] == []
    assert empty["pathway_fleet"]["cross_process_request_ids"] == []
    bare = {"traceEvents": [{"ph": "B", "pid": 0, "tid": 0, "ts": 1.0,
                             "name": "x", "args": {}},
                            {"ph": "E", "pid": 0, "tid": 0, "ts": 2.0,
                             "name": "x"}]}
    merged = fo.merge_traces([bare, {"not": "a trace"}])
    # the metaless payload merges with offset 0 and an anonymous name
    assert len(merged["pathway_fleet"]["processes"]) == 1
    assert any(e["ph"] == "B" for e in merged["traceEvents"])


def test_merge_traces_nesting_preserved_per_process():
    """B/E nesting is per-(pid, tid): merging two processes that each
    nest correctly must yield a merged file that still validates under
    the PR-5 checker keyed by (pid, tid)."""
    def proc(epoch):
        return {
            "traceEvents": [
                {"ph": "B", "pid": 0, "tid": 0, "ts": 10.0,
                 "name": "tick 1", "args": {}},
                {"ph": "B", "pid": 0, "tid": 0, "ts": 11.0, "name": "op",
                 "args": {}},
                {"ph": "E", "pid": 0, "tid": 0, "ts": 12.0, "name": "op"},
                {"ph": "E", "pid": 0, "tid": 0, "ts": 13.0,
                 "name": "tick 1"},
            ],
            "pathway_meta": {"pid": 1, "process": "p", "role": "primary",
                             "epoch_wall_us": epoch},
        }

    merged = fo.merge_traces([proc(1e6), proc(5e6)])
    stacks: dict = {}
    for ev in merged["traceEvents"]:
        key = (ev["pid"], ev.get("tid", 0))
        if ev["ph"] == "B":
            stacks.setdefault(key, []).append(ev["name"])
        elif ev["ph"] == "E":
            assert stacks.get(key), f"E without B: {ev}"
            assert stacks[key].pop() == ev["name"]
    assert all(not s for s in stacks.values())


def test_router_request_log_chrome_events_shape():
    log = fo.RouterRequestLog()
    span = log.start("rid-1", "/q")
    span.note_routed()
    t = time.perf_counter()
    span.note_attempt("r-dead", t, ok=False)
    span.note_attempt("r-live", time.perf_counter(), ok=True)
    log.finish(span, 200, "r-live")
    events = log.chrome_trace_events()
    b = [e for e in events if e["ph"] == "b"]
    e_ = [e for e in events if e["ph"] == "e"]
    assert len(b) == len(e_) == 3  # request span + forward + failover
    top = next(ev for ev in b if ev["name"] == "req rid-1")
    assert top["args"]["request_id"] == "rid-1"
    assert top["args"]["failovers"] == 1
    assert {ev["name"] for ev in b} == {"req rid-1", "forward r-dead",
                                        "failover r-live"}
    summary = log.stage_summary()
    assert summary["failover"]["sum_ms"] >= 0.0


def test_trace_merge_cli(tmp_path):
    from click.testing import CliRunner

    from pathway_tpu.cli import cli

    (tmp_path / "router.json").write_text(json.dumps(_router_payload()))
    (tmp_path / "r2.json").write_text(json.dumps(_serving_payload()))
    (tmp_path / "junk.json").write_text("{\"no\": \"trace\"}")
    runner = CliRunner()
    res = runner.invoke(cli, ["trace-merge", str(tmp_path)])
    assert res.exit_code == 0, res.output
    merged = json.loads((tmp_path / "fleet_trace.json").read_text())
    assert merged["pathway_fleet"]["cross_process_request_ids"] == ["abc"]
    assert len(merged["pathway_fleet"]["processes"]) == 2
    # idempotent over its own output: a re-run must not merge the merge
    res = runner.invoke(cli, ["trace-merge", str(tmp_path)])
    assert res.exit_code == 0, res.output
    merged2 = json.loads((tmp_path / "fleet_trace.json").read_text())
    assert len(merged2["pathway_fleet"]["processes"]) == 2


# ---------------------------------------------------------------------------
# perf-trajectory watch
# ---------------------------------------------------------------------------

def _seed(path, leg, metric, values):
    for v in values:
        fo.append_bench_history(leg, {metric: v}, path=str(path),
                                sha="deadbeef")


def test_history_append_and_read(tmp_path):
    path = tmp_path / "hist.jsonl"
    n = fo.append_bench_history(
        "etl", {"etl_rows_per_s": 100.0, "skip_me": "text",
                "flag": True, "count": 7}, path=str(path), sha="abc123")
    assert n == 2  # the string and the bool are skipped
    # a torn tail line is skipped, not fatal
    with open(path, "a") as f:
        f.write('{"leg": "etl", "metric": "torn')
    rows = fo.bench_history_rows(str(path))
    assert [(r["metric"], r["value"]) for r in rows] == \
        [("count", 7.0), ("etl_rows_per_s", 100.0)]
    assert all(r["sha"] == "abc123" for r in rows)


def test_regression_flags_seeded_drop_not_noise(tmp_path):
    path = tmp_path / "hist.jsonl"
    _seed(path, "etl", "etl_rows_per_s", [100, 104, 97, 101, 99])
    assert fo.check_regressions(str(path)) == []
    # within-band noise passes...
    _seed(path, "etl", "etl_rows_per_s", [85])
    assert fo.check_regressions(str(path)) == []
    # ...a genuine drop past the band is flagged against the MEDIAN
    _seed(path, "etl", "etl_rows_per_s", [40])
    regs = fo.check_regressions(str(path))
    assert len(regs) == 1
    r = regs[0]
    assert (r["leg"], r["metric"]) == ("etl", "etl_rows_per_s")
    assert r["direction"] == "higher" and r["ratio"] < 0.65


def test_regression_lower_better_and_young_series(tmp_path):
    path = tmp_path / "hist.jsonl"
    # young series (fewer than min_prior prior points) never gates
    _seed(path, "serving", "knn_p50_e2e_ms", [5.0, 90.0])
    assert fo.check_regressions(str(path)) == []
    _seed(path, "serving", "knn_p50_e2e_ms", [5.1, 4.9])
    # now 3 prior points exist and the newest (4.9) is fine
    assert fo.check_regressions(str(path), window=2) == []
    _seed(path, "serving", "knn_p50_e2e_ms", [30.0])
    regs = fo.check_regressions(str(path))
    assert regs and regs[0]["direction"] == "lower"


def test_regression_tolerance_band_and_unwatched(tmp_path):
    path = tmp_path / "hist.jsonl"
    _seed(path, "x", "docs_per_s", [100, 100, 100, 80])
    # 20% drop: flagged at a 10% band, passes at the default 35%
    assert fo.check_regressions(str(path)) == []
    assert fo.check_regressions(str(path), tolerance=0.10)
    # per-metric override wins over the default
    assert fo.check_regressions(
        str(path), tolerances={"docs_per": 0.05})
    # a metric with no recognizable direction is unwatched
    _seed(path, "x", "mystery_number", [1, 1, 1, 1000])
    flagged = {r["metric"] for r in fo.check_regressions(
        str(path), tolerance=0.10)}
    assert "mystery_number" not in flagged


def test_regression_zero_median_series(tmp_path):
    path = tmp_path / "hist.jsonl"
    _seed(path, "fleet", "replica_lost_queries", [0, 0, 0, 0])
    assert fo.check_regressions(str(path)) == []
    _seed(path, "fleet", "replica_lost_queries", [3])
    regs = fo.check_regressions(str(path))
    assert regs and regs[0]["metric"] == "replica_lost_queries"
    assert regs[0]["ratio"] is None  # infinite: any loss off a zero floor


def test_metric_direction_heuristics():
    assert fo.metric_direction("docs_per_s") == "higher"
    assert fo.metric_direction("etl_scaleout_efficiency") == "higher"
    assert fo.metric_direction("framework_vs_raw_ratio") == "higher"
    assert fo.metric_direction("knn_p50_e2e_ms") == "lower"
    assert fo.metric_direction("replica_ready_snapshot_s_1000") == "lower"
    assert fo.metric_direction("replica_max_staleness_ticks") == "lower"
    assert fo.metric_direction("router_replica_p50_skew_ms") == "lower"
    assert fo.metric_direction("knn_n_vectors") is None


# ---------------------------------------------------------------------------
# atomic_write_json directory fsync (satellite bugfix)
# ---------------------------------------------------------------------------

def test_atomic_write_fsyncs_containing_directory(tmp_path, monkeypatch):
    """The rename's durability lives in the directory's metadata: the
    write must fsync the containing dir after os.replace (the ext4
    crash-right-after-rename hole)."""
    synced_dirs: list[str] = []
    real_fsync = os.fsync

    def spy_fsync(fd):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
            if os.path.isdir(target):
                synced_dirs.append(target)
        except OSError:
            pass
        return real_fsync(fd)

    monkeypatch.setattr(os, "fsync", spy_fsync)
    path = tmp_path / "evidence.json"
    atomic_write_json(str(path), {"v": 1})
    assert json.loads(path.read_text()) == {"v": 1}
    assert str(tmp_path) in synced_dirs


def test_atomic_write_dirsync_crash_keeps_renamed_file(tmp_path):
    """Fault-point pin: a crash landing between the rename and the dir
    fsync (fs.atomic_write.dirsync) surfaces as the injected error, but
    the NEW content is already at the path — the rename itself happened
    before the crash window."""
    path = tmp_path / "evidence.json"
    atomic_write_json(str(path), {"v": 1})
    with faults.arm("fs.atomic_write.dirsync", faults.FailNTimes(1)):
        with pytest.raises(faults.InjectedFault):
            atomic_write_json(str(path), {"v": 2})
    assert json.loads(path.read_text()) == {"v": 2}
    # no tmp litter from the fault path
    assert [p.name for p in tmp_path.iterdir()] == ["evidence.json"]
    # disarmed, the write is clean again
    atomic_write_json(str(path), {"v": 3})
    assert json.loads(path.read_text()) == {"v": 3}


def test_bench_history_appends_survive_dirsync_fault(tmp_path):
    """BENCH_HISTORY appends are plain line appends (no rename), and the
    lastgood checkpoint path keeps its file through an injected dirsync
    crash — the satellite's end-to-end shape via bench's own writer."""
    import bench

    lastgood = tmp_path / "BENCH_LASTGOOD.json"
    old_state = dict(bench._LASTGOOD_STATE)
    bench._LASTGOOD_STATE.clear()
    old_env = os.environ.get("BENCH_LASTGOOD_PATH")
    os.environ["BENCH_LASTGOOD_PATH"] = str(lastgood)
    try:
        bench._write_lastgood({"etl_rows_per_s": 123.0})
        assert json.loads(lastgood.read_text())["result"][
            "etl_rows_per_s"] == 123.0
        with faults.arm("fs.atomic_write.dirsync", faults.FailNTimes(1)):
            # _write_lastgood swallows (evidence must never kill a leg)
            bench._write_lastgood({"etl_rows_per_s": 124.0})
        # the rename preceded the injected crash: newest value is live
        assert json.loads(lastgood.read_text())["result"][
            "etl_rows_per_s"] == 124.0
    finally:
        bench._LASTGOOD_STATE.clear()
        bench._LASTGOOD_STATE.update(old_state)
        if old_env is None:
            os.environ.pop("BENCH_LASTGOOD_PATH", None)
        else:
            os.environ["BENCH_LASTGOOD_PATH"] = old_env
