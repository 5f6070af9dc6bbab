"""A waiting request wakes the commit loop (engine/streaming.py
``_wait_for_tick``): the autocommit period is the longest a request waits
for a tick, not what it waits on average. A push from an ingest source
wakes nobody, a tick that a request woke drains the serving sources alone
(ingest keeps the period's cadence, its seals and its budget), a stop
request still ends the wait at once, and a cluster keeps the period on
every process. The ``tick`` span and ``/metrics`` say what woke each tick.

Wall-clock assertions here compare times an order of magnitude apart."""

from __future__ import annotations

import json
import threading
import time
import urllib.request

import pytest

import pathway_tpu as pw
from pathway_tpu.engine import qos
from pathway_tpu.engine import streaming as _streaming
from pathway_tpu.internals import schema as sch
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.io.http import PathwayWebserver, rest_connector


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    G.clear()
    monkeypatch.setenv("PATHWAY_FLIGHT_RECORDER", "1")
    yield
    G.clear()


class _LoneCluster:
    """A cluster of one process: the runtime's cluster paths with no peer
    to exchange with."""

    n_processes = 1
    process_id = 0
    peers: dict = {}

    def exchange(self, tag, msgs):
        return {}


def _subject(rows: int, every_s: float):
    from pathway_tpu.io.python import ConnectorSubject

    class _Rows(ConnectorSubject):
        def run(self) -> None:
            for i in range(rows):
                time.sleep(every_s)
                self.next(x=i)

    return _Rows()


def _build(period_ms: int, *, ingest=None, cluster=None):
    """A ``rest_connector`` that upper-cases its query, and beside it
    (``ingest``: a subject) a python source summed by a reducer; the
    runtime as ``pw.run`` builds it, with ``period_ms`` as the period."""
    from pathway_tpu.internals.runner import GraphRunner

    ws = PathwayWebserver(host="127.0.0.1", port=0)
    table, writer = rest_connector(
        webserver=ws, route="/q", schema=sch.schema_from_types(query=str),
        methods=("POST",), delete_completed_queries=True,
        autocommit_duration_ms=period_ms)
    writer(table.select(result=pw.apply(str.upper, table.query)))
    seen: list[int] = []
    if ingest is not None:
        rows = pw.io.python.read(ingest, schema=sch.schema_from_types(x=int),
                                 autocommit_duration_ms=period_ms)
        pw.io.subscribe(rows, lambda key, row, time, is_addition:
                        seen.append(row["x"]))
    runner = GraphRunner()
    for binder in G.output_binders:
        binder(runner)
    rt = _streaming.StreamingRuntime(runner, default_commit_ms=period_ms,
                                     cluster=cluster)
    return rt, ws, seen


class _Running:
    """The runtime on a thread, until the ``with`` block ends."""

    def __init__(self, rt, ws):
        self.rt, self.ws = rt, ws
        self.errors: list = []
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        try:
            self.rt.run()
        except Exception as e:  # surfaced by __exit__
            self.errors.append(e)

    def __enter__(self):
        self.thread.start()
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline and not (
                self.ws._started.is_set() and self.ws.port):
            time.sleep(0.01)
        assert self.ws.port, f"the webserver never started: {self.errors}"
        return self

    def __exit__(self, *exc):
        self.rt.stop()
        self.thread.join(10.0)
        assert not self.thread.is_alive()
        assert not self.errors, self.errors

    def ask(self, query: str) -> tuple[str, float]:
        """The answer, and the seconds it took."""
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.ws.port}/q",
            data=json.dumps({"query": query}).encode(), method="POST",
            headers={"Content-Type": "application/json"})
        t0 = time.monotonic()
        with urllib.request.urlopen(req, timeout=30) as resp:
            body = resp.read().decode()
        return body, time.monotonic() - t0


def _ticks(rt) -> list[dict]:
    """The ``tick`` spans' counts, oldest first, each with its start."""
    return [dict(sp[5], start=sp[1], cause=sp[3])
            for sp in rt.recorder.spans() if sp[0] == "tick"]


def _drains(rt) -> dict:
    """cause -> rows by source of every ``tick.drain`` span."""
    return {sp[3]: sp[5] or {} for sp in rt.recorder.spans()
            if sp[0] == "tick.drain"}


# ---------------------------------------------------------------------------
# one run with requests under a period of 5 s: the tick span, the request's
# queue stage, the counts on /metrics
# ---------------------------------------------------------------------------

PERIOD_MS = 5000
_served: dict = {}


def _serve_three() -> dict:
    """Three requests, one after the other, to a runtime whose period is
    5 s; what the run left behind (made once, read by several tests)."""
    if _served:
        return _served
    from pathway_tpu.engine.http_server import MonitoringHttpServer

    rt, ws, _seen = _build(PERIOD_MS)
    with _Running(rt, ws) as run:
        answers = [run.ask(q) for q in ("alpha", "beta", "gamma")]
        metrics = MonitoringHttpServer(rt, port=0).metrics_payload()
    _served.update(
        answers=answers, ticks=_ticks(rt), metrics=metrics,
        requests=rt.recorder.requests.trace_spans(),
        woken=dict(rt.ticks_woken_by))
    return _served


def test_a_request_is_picked_up_at_once_and_its_tick_says_request():
    got = _serve_three()
    assert [a for a, _s in got["answers"]] == ["ALPHA", "BETA", "GAMMA"]
    # a tenth of the period, for answers that used to take half of it
    assert all(s < 0.5 for _a, s in got["answers"]), got["answers"]
    with_requests = [t for t in got["ticks"] if t["requests"]]
    assert len(with_requests) == 3
    assert all(t["woken_by"] == "request" for t in with_requests), \
        with_requests
    assert all(r["stages"]["queue"] < 500.0 for r in got["requests"]), \
        got["requests"]


def test_metrics_show_both_counts():
    from tests.test_monitoring_http import _parse_samples

    got = _serve_three()
    lines = got["metrics"].splitlines()
    assert "# TYPE pathway_tpu_ticks_total counter" in lines
    counts = {labels["woken_by"]: value
              for family, labels, value in _parse_samples(lines)
              if family == "pathway_tpu_ticks_total"}
    assert set(counts) == {"period", "request"}
    # each request woke a tick; so may its retraction's neighbour have
    assert counts["request"] >= 3
    assert counts["request"] <= got["woken"]["request"]
    assert counts["period"] <= got["woken"]["period"]


def test_metrics_of_a_runtime_without_the_counter_omit_the_family():
    from pathway_tpu.engine.http_server import MonitoringHttpServer
    from tests.test_monitoring_http import _FakeRuntime

    payload = MonitoringHttpServer(_FakeRuntime(), port=0).metrics_payload()
    assert "pathway_tpu_ticks_total" not in payload


# ---------------------------------------------------------------------------
# what does not wake the loop
# ---------------------------------------------------------------------------

def test_with_no_request_ticks_keep_the_period_and_say_period():
    period_s = 0.2
    rt, ws, _seen = _build(int(period_s * 1e3))
    with _Running(rt, ws):
        time.sleep(1.5)
    ticks = _ticks(rt)
    assert len(ticks) >= 2
    assert all(t["woken_by"] == "period" for t in ticks), ticks
    assert rt.ticks_woken_by["request"] == 0
    assert rt.ticks_woken_by["period"] >= len(ticks)
    # the wait is never shorter than the period
    starts = [t["start"] for t in ticks]
    assert all(b - a >= period_s * 0.99 for a, b in zip(starts, starts[1:]))


def test_a_push_from_an_ingest_source_does_not_wake_the_loop():
    """150 rows at 100 a second under a period of half a second: were a
    row to wake the loop there would be a tick a row."""
    rt, ws, seen = _build(500, ingest=_subject(150, 0.01))
    assert [s.wake is not None for _n, s, _d in rt.sessions] \
        == [True, False]
    with _Running(rt, ws):
        deadline = time.monotonic() + 30.0
        while len(seen) < 150 and time.monotonic() < deadline:
            time.sleep(0.05)
    assert sorted(seen) == list(range(150))
    ticks = _ticks(rt)
    assert all(t["woken_by"] == "period" for t in ticks), ticks
    assert rt.ticks_woken_by["request"] == 0
    assert len([t for t in ticks if t["rows"]]) <= 15


def test_a_tick_that_a_request_woke_leaves_ingest_to_the_period():
    """Requests while an ingest source pushes: the ticks they wake take
    no row of it, and every row still arrives, by the period's ticks."""
    rt, ws, seen = _build(1000, ingest=_subject(300, 0.01))
    with _Running(rt, ws) as run:
        time.sleep(0.3)
        for i in range(8):
            assert run.ask(f"q{i}")[0] == f"Q{i}"
            time.sleep(0.2)
        deadline = time.monotonic() + 30.0
        while len(seen) < 300 and time.monotonic() < deadline:
            time.sleep(0.05)
    assert sorted(seen) == list(range(300))
    ticks, drains = _ticks(rt), _drains(rt)
    woken = [t for t in ticks if t["woken_by"] == "request"]
    assert len(woken) >= 6, ticks
    for t in woken:
        assert all(name.startswith("rest-") for name in drains[t["cause"]]), \
            (t, drains[t["cause"]])
    by_period = sum(n for t in ticks if t["woken_by"] == "period"
                    for name, n in drains.get(t["cause"], {}).items()
                    if not name.startswith("rest-"))
    assert by_period == 300


def test_a_stop_request_ends_the_wait_at_once():
    rt, ws, _seen = _build(60_000)
    with _Running(rt, ws) as run:
        time.sleep(0.2)
        t0 = time.monotonic()
        rt.stop()
        run.thread.join(10.0)
        assert not run.thread.is_alive()
        assert time.monotonic() - t0 < 6.0


def test_under_a_cluster_a_request_waits_for_the_period():
    """``_tick_sync`` is a lock-step exchange: a process woken alone would
    block in it until its peers' period ends. No session of a clustered
    runtime wakes the loop, and a request rides the period's tick."""
    rt, ws, _seen = _build(300, cluster=_LoneCluster())
    assert all(s.wake is None for _n, s, _d in rt.sessions)
    with _Running(rt, ws) as run:
        assert [run.ask(q)[0] for q in ("a", "b", "c")] == ["A", "B", "C"]
    ticks = _ticks(rt)
    assert [t["woken_by"] for t in ticks if t["requests"]] == ["period"] * 3
    assert rt.ticks_woken_by["request"] == 0


# ---------------------------------------------------------------------------
# the drain of a tick that a request woke, and what it tells the budgets
# ---------------------------------------------------------------------------

def _pushed(persisted: bool):
    """A runtime that is not running, with three rows in its ingest
    session and one request in its serving session."""
    from pathway_tpu.engine.persistence import _RecordingSession

    rt, _ws, _seen = _build(1000, ingest=_subject(0, 0.0))
    (_n0, serving, ds0), (_n1, ingest, ds1) = rt.sessions
    assert _streaming._is_serving(ds0) and not _streaming._is_serving(ds1)
    into = ingest
    if persisted:
        into = rt._drain_proxies[1] = _RecordingSession(ingest, 0)
    for i in range(3):
        into.push(i, (i,), 1)
    serving.push("request", ("q",), 1)
    return rt, serving, ingest, into


@pytest.mark.parametrize("persisted", [False, True],
                         ids=["plain", "persisted"])
def test_a_request_s_tick_drains_the_serving_sources_alone(persisted):
    rt, serving, ingest, into = _pushed(persisted)
    try:
        assert rt._wake.is_set()  # the request set it; the rows did not
        any_data, all_closed, _pushes = rt._drain_and_forward(
            7, serving_only=True)
        assert any_data and not all_closed
        assert rt._last_drain == (0, 1, False)
        assert serving.backlog() == 0 and ingest.backlog() == 3
        if persisted:
            # sealed at t == drained at t: nothing of the ingest source
            # was drained at 7, so nothing of it is sealed at 7
            assert into._seals == [] and len(into.pending) == 3
        # the period's tick takes them, and seals what it took
        rt._drain_and_forward(8)
        assert rt._last_drain == (3, 0, False)
        assert ingest.backlog() == 0
        if persisted:
            assert into._seals == [(8, 3)]
    finally:
        rt.scheduler.close()


def test_a_retraction_does_not_wake_the_loop():
    rt, serving, _ingest, _into = _pushed(False)
    try:
        rt._wake.clear()
        serving.push("request", ("q",), -1)
        assert not rt._wake.is_set()
        serving.push("another", ("q",), 1)
        assert rt._wake.is_set()
    finally:
        rt.scheduler.close()


def test_the_wait_says_what_ended_it():
    rt, _serving, _ingest, _into = _pushed(False)
    try:
        far = time.monotonic() + 60.0
        assert rt._wait_for_tick(far) == "request"   # pushed by _pushed
        assert not rt._wake.is_set()
        t0 = time.monotonic()
        assert rt._wait_for_tick(t0 + 0.05) == "period"
        assert time.monotonic() - t0 >= 0.05
        # a request and a period that is over: the period's tick, which
        # drains the serving sources too
        rt._wake.set()
        assert rt._wait_for_tick(time.monotonic() - 1.0) == "period"
        assert not rt._wake.is_set()
        rt.stop()
        assert rt._wait_for_tick(far) == "stop"
    finally:
        rt.scheduler.close()


def test_a_request_behind_a_leg_in_flight_waits_for_it_to_retire():
    """A leg submitted behind one in flight would only queue: the loop
    sleeps on until the bridge's worker says the leg has retired, so the
    arrivals of that time ride one tick; the period still ends the wait."""
    rt, _serving, _ingest, _into = _pushed(False)
    try:
        depth = [1]
        rt.scheduler.bridge_depth = lambda: depth[0]
        t0 = time.monotonic()
        assert rt._wait_for_tick(t0 + 0.1) == "period"
        assert time.monotonic() - t0 >= 0.1
        got: list[str] = []
        rt._wake.set()
        waiter = threading.Thread(
            target=lambda: got.append(
                rt._wait_for_tick(time.monotonic() + 60.0)), daemon=True)
        waiter.start()
        waiter.join(0.3)
        assert waiter.is_alive() and not got       # held behind the leg
        depth[0] = 0
        rt._on_watermark_advance(5)                # the bridge's worker
        waiter.join(10.0)
        assert got == ["request"]
        # nobody waiting: a retiring leg wakes nobody
        rt._on_watermark_advance(6)
        assert not rt._wake.is_set()
    finally:
        rt.scheduler.close()


class _Limiter:
    """Stands for either ingest budget: the controller of a runtime with
    QoS armed, or ``DeviceBackpressure``."""

    backpressure_active = False

    def __init__(self):
        self.looks: list[dict] = []

    def on_tick(self, *args, **kw):
        self.looks.append(kw)


@pytest.mark.parametrize("armed", [True, False],
                         ids=["qos", "backpressure"])
def test_either_ingest_budget_is_fed_once_an_interval(armed):
    """Both budgets are reckoned in commit intervals: a request's tick
    consults and feeds neither, and its queries are counted into the
    period's look, which so sees one interval's queries, as when the
    period's tick drained them itself (so that a look on an interval
    which served queries stays no reading of an ingest row's cost)."""
    rt, _serving, _ingest, _into = _pushed(False)
    try:
        limiter = _Limiter()
        if armed:
            rt.qos = limiter
        else:
            rt._backpressure = limiter
            rt.scheduler.bridge_stats = lambda: {"exec_ms": 0.0}
        key = "queries_in_tick" if armed else "query_rows"
        rt._last_drain = (0, 2, False)
        rt._tick_feedback(5, 1.0, 0.05, by_request=True)
        rt._last_drain = (0, 1, False)
        rt._tick_feedback(6, 1.0, 0.05, by_request=True)
        assert limiter.looks == []
        rt._last_drain = (4, 1, True)
        rt._tick_feedback(7, 1.0, 0.05)
        assert len(limiter.looks) == 1
        assert limiter.looks[0]["ingest_rows"] == 4
        assert limiter.looks[0][key] == 4
        rt._last_drain = (4, 0, False)
        rt._tick_feedback(8, 1.0, 0.05)
        assert limiter.looks[1][key] == 0
    finally:
        rt.qos = None
        rt.scheduler.close()


def test_an_interval_that_served_requests_is_no_reading_of_a_row_s_cost():
    """What the period's look makes of the queries counted into it: the
    legs of the requests' ticks ran between two looks, their time is in
    ``exec_ms``, and ``DeviceBackpressure`` takes no cost from it."""
    def bridge(**kw):
        return dict(dict(resolved_watermark=0, exec_ms=0.0,
                         submits_blocked=0, depth=0), **kw)

    for queries, sampled in ((3, False), (0, True)):
        bp = qos.DeviceBackpressure(0.05)
        bp.on_tick(1, ingest_rows=8, query_rows=queries, deferred=True,
                   bridge=bridge())
        bp.on_tick(3, ingest_rows=8, query_rows=0, deferred=True,
                   bridge=bridge(resolved_watermark=1, exec_ms=40.0))
        assert (bp._cost.ms_per_row is not None) == sampled
