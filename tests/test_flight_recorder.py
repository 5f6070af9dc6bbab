"""Flight recorder: per-operator tick tracing, Chrome-trace export, and
stall attribution (engine/flight_recorder.py; reference: the OTLP span +
latency-gauge surface of src/engine/telemetry.rs:196-366).

Proves the acceptance contract:
- a run with PATHWAY_TRACE_PATH produces a Perfetto-loadable trace with
  host and device tracks and user-frame attribution on operator spans;
- the recorder is OFF by default (scheduler carries None — the one-branch
  hot path) and PATHWAY_FLIGHT_RECORDER=0 force-disables everything;
- a seeded device-leg hang is named — operator, leg, user frame — in the
  watchdog's post-mortem dump.
"""

from __future__ import annotations

import json
import logging
import threading
import time

import pytest

import pathway_tpu as pw
from pathway_tpu.engine.flight_recorder import FlightRecorder, attach_note
from pathway_tpu.internals.parse_graph import G


@pytest.fixture(autouse=True)
def _fresh():
    G.clear()
    yield
    G.clear()


class _FakeOp:
    pass


class _FakeNode:
    def __init__(self, id, name, trace=None):
        self.id = id
        self.name = name
        self.op = _FakeOp()
        self.trace = trace


# ---------------------------------------------------------------------------
# gating: off by default, env overrides
# ---------------------------------------------------------------------------

def test_recorder_off_by_default(monkeypatch):
    monkeypatch.delenv("PATHWAY_TRACE_PATH", raising=False)
    monkeypatch.delenv("PATHWAY_FLIGHT_RECORDER", raising=False)
    assert FlightRecorder.from_env() is None
    from pathway_tpu.internals.runner import GraphRunner

    t = pw.debug.table_from_markdown("""
    a
    1
    """)
    runner = GraphRunner()
    runner.capture(t.select(b=t.a + 1))
    runner.run_batch()
    assert runner._scheduler.recorder is None


def test_from_env_gating(monkeypatch, tmp_path):
    monkeypatch.setenv("PATHWAY_TRACE_PATH", str(tmp_path / "t.json"))
    rec = FlightRecorder.from_env()
    assert rec is not None and rec.enabled
    assert rec.trace_path == str(tmp_path / "t.json")
    # force-off beats everything
    monkeypatch.setenv("PATHWAY_FLIGHT_RECORDER", "0")
    assert FlightRecorder.from_env() is None
    assert FlightRecorder.from_env(auto_on=True) is None
    # observable surfaces turn it on without a trace path
    monkeypatch.delenv("PATHWAY_FLIGHT_RECORDER")
    monkeypatch.delenv("PATHWAY_TRACE_PATH")
    assert FlightRecorder.from_env() is None
    rec = FlightRecorder.from_env(auto_on=True)
    assert rec is not None and rec.enabled and rec.trace_path is None


# ---------------------------------------------------------------------------
# ring buffer, histograms, dump
# ---------------------------------------------------------------------------

def test_tail_events_keeps_last_n_ticks():
    rec = FlightRecorder(buffer_events=1000)
    rec.enabled = True
    node = _FakeNode(0, "op")
    for tick in range(10):
        for _ in range(3):
            rec.record(tick, node, "host", 0.0, 1.0, 1, 1)
    tail = rec.tail_events(2)
    assert sorted({ev[0] for ev in tail}) == [8, 9]
    assert len(tail) == 6
    assert len(rec.tail_events(None)) == 30


def test_dump_tail_names_inflight_operator_and_frame():
    from pathway_tpu.internals.trace import Trace

    rec = FlightRecorder()
    rec.enabled = True
    trace = Trace("pipeline.py", 42, "build", "t.select(score=udf(...))")
    stuck = _FakeNode(7, "map:score", trace=trace)
    rec.record(1, _FakeNode(0, "source"), "host", 0.0, 0.5, 4, 4)
    rec.mark_op(2, stuck, "device")  # stepping… and never returning
    dump = rec.dump_tail()
    assert "tick 1 [host] source" in dump
    assert "IN FLIGHT" in dump and "map:score" in dump
    assert "[device]" in dump
    assert 'File "pipeline.py", line 42' in dump
    info = rec.inflight_summary()
    assert info["operator"] == "map:score" and info["leg"] == "device"
    # other threads churning through their own steps (host legs, sharded
    # pool replicas) must NOT evict the older stuck marker: slots are
    # keyed per stepping thread, and the hung thread never clears its own
    def churn():
        rec.mark_op(3, _FakeNode(1, "hostop"), "host")
        rec.clear_op()

    th = threading.Thread(target=churn)
    th.start()
    th.join()
    assert rec.inflight_summary()["operator"] == "map:score"


def test_attach_note_pre_311_storage():
    e = ValueError("x")
    attach_note(e, "note one")
    attach_note(e, "note one")  # idempotent
    attach_note(e, "note two")
    assert list(getattr(e, "__notes__", [])) == ["note one", "note two"]


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------

def _check_nesting(events):
    """B/E pairs per tid must balance and nest like a call stack."""
    stacks: dict = {}
    for ev in events:
        if ev["ph"] == "B":
            stacks.setdefault(ev["tid"], []).append(ev["name"])
        elif ev["ph"] == "E":
            stack = stacks.setdefault(ev["tid"], [])
            assert stack, f"E without B on tid {ev['tid']}: {ev}"
            top = stack.pop()
            assert top == ev["name"], \
                f"mis-nested span: E {ev['name']!r} closes B {top!r}"
    for tid, stack in stacks.items():
        assert not stack, f"unclosed spans on tid {tid}: {stack}"


def test_batch_trace_file_is_valid_and_nested(monkeypatch, tmp_path):
    path = tmp_path / "trace.json"
    monkeypatch.setenv("PATHWAY_TRACE_PATH", str(path))
    t = pw.debug.table_from_markdown("""
    a | b
    1 | 2
    3 | 4
    """)
    out = t.select(c=t.a + t.b)
    pw.debug.compute_and_print(out)
    data = json.loads(path.read_text())
    events = data["traceEvents"]
    thread_names = {e["args"]["name"] for e in events
                    if e["ph"] == "M" and e["name"] == "thread_name"}
    assert thread_names == {"host leg", "device leg"}
    # fleet identity (PR 14): the process track is named role:process and
    # the payload carries the mergeable clock-anchor meta block
    proc_names = {e["args"]["name"] for e in events
                  if e["ph"] == "M" and e["name"] == "process_name"}
    assert len(proc_names) == 1 and next(iter(proc_names)).count(":") >= 1
    meta = data["pathway_meta"]
    assert meta["role"] and meta["process"]
    assert meta["epoch_wall_us"] > 0
    _check_nesting(events)
    b_ops = [e for e in events if e["ph"] == "B"
             and not e["name"].startswith("tick ")]
    assert b_ops, "no operator spans recorded"
    # operator spans carry user-frame attribution pointing at THIS file
    framed = [e for e in b_ops if "user_frame" in e.get("args", {})]
    assert any("test_flight_recorder.py" in e["args"]["user_frame"]
               for e in framed)
    # rows ride along
    assert all({"rows_in", "rows_out"} <= set(e["args"]) for e in b_ops)


def test_streaming_trace_has_device_track(monkeypatch, tmp_path):
    """A pipelined streaming run writes device-leg spans on their own
    track, inside the recorded ``bridge.leg`` span of their tick, which
    carries the leg's queue-wait/exec attribution."""
    import numpy as np

    path = tmp_path / "trace.json"
    monkeypatch.setenv("PATHWAY_DEVICE_INFLIGHT", "2")

    @pw.udf(batch=True, device=True, deterministic=True, return_type=int)
    def dev_len(ws):
        import jax.numpy as jnp

        arr = jnp.asarray(np.asarray([len(w) for w in ws], np.int32))
        return [int(v) for v in np.asarray(arr)]

    class Subj(pw.io.python.ConnectorSubject):
        def run(self):
            for w in ["aa", "bbb", "c"]:
                self.next(word=w)

    t = pw.io.python.read(Subj(), schema=pw.schema_from_types(word=str),
                          autocommit_duration_ms=10)
    t = t.select(word=t.word, wl=dev_len(t.word))
    pw.io.subscribe(t, lambda *a, **k: None)
    pw.run(trace_path=str(path))
    data = json.loads(path.read_text())
    events = data["traceEvents"]
    _check_nesting(events)
    host_b = [e for e in events if e["ph"] == "B" and e.get("cat") == "host"]
    dev_b = [e for e in events if e["ph"] == "B" and e.get("cat") == "device"]
    assert host_b and dev_b, "expected spans on both tracks"
    assert {e["tid"] for e in host_b} != {e["tid"] for e in dev_b}
    legs = [e for e in events if e["ph"] == "B" and e["tid"] == 1
            and e["name"].startswith("bridge.leg ")]
    assert legs and all({"queue_wait_ms", "exec_ms"} <= set(e["args"])
                        for e in legs)
    assert any(e["name"].startswith("map:") for e in dev_b)
    # the operator slices of a leg lie inside the leg's own slice
    ends = {e["name"]: e["ts"] for e in events
            if e["ph"] == "E" and e["tid"] == 1}
    op = next(e for e in dev_b if e["name"].startswith("map:"))
    leg = next(e for e in legs if e["args"]["cause"][1] == op["args"]["tick"])
    assert leg["ts"] <= op["ts"] <= ends[leg["name"]]


# ---------------------------------------------------------------------------
# seeded device-leg hang → post-mortem names the stuck operator
# ---------------------------------------------------------------------------

def test_seeded_device_leg_hang_named_in_postmortem(monkeypatch, caplog):
    """A device leg that hangs stalls the commit loop (backpressure), the
    watchdog fires, and its post-mortem dump names the stuck operator with
    its user frame — instead of a timeout naming nothing."""
    import numpy as np

    monkeypatch.setenv("PATHWAY_DEVICE_INFLIGHT", "2")
    monkeypatch.setenv("PATHWAY_FLIGHT_RECORDER", "1")
    release = threading.Event()

    @pw.udf(batch=True, device=True, deterministic=True, return_type=int)
    def stuck_score(ws):
        release.wait(20.0)  # the seeded hang: blocks until the test says go
        return [len(w) for w in np.asarray(ws, dtype=object)]

    class Subj(pw.io.python.ConnectorSubject):
        def run(self):
            self.next(word="hello")

    t = pw.io.python.read(Subj(), schema=pw.schema_from_types(word=str),
                          autocommit_duration_ms=10)
    t = t.select(word=t.word, s=stuck_score(t.word))
    pw.io.subscribe(t, lambda *a, **k: None)

    fired = threading.Event()

    class _Spy(logging.Handler):
        messages: list = []

        def emit(self, record):
            msg = record.getMessage()
            type(self).messages.append(msg)
            if "commit loop has not ticked" in msg:
                fired.set()
                release.set()  # unblock so the run can finish cleanly

    spy = _Spy()
    _Spy.messages = []
    sup_logger = logging.getLogger("pathway_tpu.engine.supervisor")
    sup_logger.addHandler(spy)
    try:
        pw.run(watchdog=pw.WatchdogConfig(tick_deadline_s=0.4,
                                          poll_interval_s=0.05))
    finally:
        sup_logger.removeHandler(spy)
    assert fired.wait(0.1), "watchdog never reported the stalled commit loop"
    stall = next(m for m in _Spy.messages
                 if "commit loop has not ticked" in m)
    assert "flight recorder tail" in stall
    assert "IN FLIGHT" in stall
    assert "[device]" in stall and "map:" in stall
    assert "test_flight_recorder.py" in stall  # the user frame


# ---------------------------------------------------------------------------
# OTel span flow (API-level fake SDK: no exporter packages needed)
# ---------------------------------------------------------------------------

def test_recorded_spans_flow_through_telemetry_provider():
    spans = []

    class _Span:
        def __init__(self, name, start):
            self.name = name
            self.start = start
            self.attrs = {}
            self.end_ns = None

        def set_attribute(self, k, v):
            self.attrs[k] = v

        def end(self, end_time=None):
            self.end_ns = end_time

    class _Tracer:
        def start_span(self, name, start_time=None):
            sp = _Span(name, start_time)
            spans.append(sp)
            return sp

    class _Telemetry:
        _provider = object()  # a "real SDK pipeline is wired" marker
        tracer = _Tracer()

    rec = FlightRecorder()
    rec.enabled = True
    rec.set_telemetry(_Telemetry())
    from pathway_tpu.internals.trace import Trace

    node = _FakeNode(3, "groupby:sales",
                     trace=Trace("app.py", 7, "main", "t.groupby(...)"))
    rec.record(5, node, "device", time.perf_counter(), 12.5, 100, 4)
    assert len(spans) == 1
    sp = spans[0]
    assert sp.name == "pathway.operator.groupby:sales"
    assert sp.attrs["pathway.tick"] == 5
    assert sp.attrs["pathway.leg"] == "device"
    assert sp.attrs["pathway.rows_in"] == 100
    assert "app.py" in sp.attrs["pathway.user_frame"]
    assert sp.end_ns is not None and sp.end_ns > sp.start
    # API-only mode (no SDK provider) must NOT pay span construction
    class _ApiOnly:
        _provider = None
        tracer = _Tracer()

    rec2 = FlightRecorder()
    rec2.enabled = True
    rec2.set_telemetry(_ApiOnly())
    rec2.record(1, node, "host", 0.0, 1.0, 1, 1)
    assert len(spans) == 1


# ---------------------------------------------------------------------------
# the span store: tick, bridge-leg and connector-pass spans
# ---------------------------------------------------------------------------

def test_span_store_overlap_query_and_bound():
    from pathway_tpu.engine.flight_recorder import _SPAN_BUFFER

    rec = FlightRecorder()
    rec.enabled = True
    rec.span("tick", 1.0, 2.0, ("tick", 1), rows=3, requests=0)
    rec.span("tick", 3.0, 4.0, ("tick", 2))
    rec.span("connector.pass", 0.5, 3.5, ("pass", 0, 0), listed=2)
    name, t0, t1, cause, ident, counts = rec.spans()[0]
    assert (name, t0, t1, cause) == ("tick", 1.0, 2.0, ("tick", 1))
    assert ident == threading.get_ident()
    assert counts == {"rows": 3, "requests": 0}
    assert rec.spans()[1][5] is None  # no counts: no dict
    # overlap with [t0, t1], oldest first; an open end is unbounded
    assert [s[3] for s in rec.spans(2.5, 3.2)] == [("tick", 2),
                                                   ("pass", 0, 0)]
    assert [s[3] for s in rec.spans(t1=0.7)] == [("pass", 0, 0)]
    assert [s[3] for s in rec.spans(t0=3.8)] == [("tick", 2)]
    assert not hasattr(rec, "_legs") and not hasattr(rec, "record_leg")
    # bounded: the oldest spans go, the operator ring is its own
    for i in range(_SPAN_BUFFER + 10):
        rec.span("tick", float(i), float(i) + 0.5, ("tick", i))
    assert len(rec.spans()) == _SPAN_BUFFER
    assert rec.spans()[0][3] == ("tick", 10)
    assert rec._events.maxlen == 65_536


def test_trace_payload_derives_device_legs_from_spans():
    rec = FlightRecorder()
    rec.enabled = True
    rec.span("bridge.wait", 1.0, 1.002, ("tick", 4), depth=1)
    rec.span("bridge.leg", 1.002, 1.012, ("tick", 4))
    rec.span("tick", 0.9, 1.001, ("tick", 4), rows=1, requests=1)
    payload = rec.trace_payload()
    assert payload["device_legs"] == [
        {"tick": 4, "queue_wait_ms": 2.0, "exec_ms": 10.0}]
    by_name = {s["name"]: s for s in payload["spans"]}
    assert set(by_name) == {"bridge.wait", "bridge.leg", "tick"}
    assert by_name["tick"]["cause"] == ["tick", 4]
    assert by_name["tick"]["counts"] == {"rows": 1, "requests": 1}
    assert by_name["bridge.leg"]["dur_ms"] == 10.0
    assert by_name["tick"]["thread"] == threading.current_thread().name
    json.dumps(payload)


def test_chrome_export_draws_recorded_spans_not_a_wrapper():
    """Operator steps with no tick span around them (a batch run, a static
    feed) lie bare on their track: no slice is made up from them. A
    connector's passes get a track named after its thread, and a wait
    that overlaps the leg before it is an async event."""
    rec = FlightRecorder()
    rec.enabled = True
    rec._epoch = 0.0
    rec.record(1, _FakeNode(0, "bare"), "host", 0.10, 5.0, 1, 1)
    rec.span("tick", 1.00, 1.05, ("tick", 2), rows=1, requests=0)
    rec.span("tick.drain", 1.01, 1.02, ("tick", 2), **{"fs-0": 1})
    rec.span("tick.host", 1.02, 1.05, ("tick", 2))
    rec.record(2, _FakeNode(1, "inside"), "host", 1.03, 10.0, 1, 1)
    rec.span("bridge.wait", 1.05, 1.06, ("tick", 2), depth=1)
    rec.span("bridge.leg", 1.06, 1.10, ("tick", 2))
    rec.span("bridge.wait", 1.08, 1.10, ("tick", 3), depth=2)  # overlaps
    rec.span("bridge.leg", 1.10, 1.12, ("tick", 3))

    def reader():
        rec.span("connector.pass", 0.5, 1.5, ("pass", 0, 7), listed=9)

    th = threading.Thread(target=reader, name="src-fs-0")
    th.start()
    th.join()
    events = rec.chrome_trace_events()
    _check_nesting(events)
    tracks = {e["tid"]: e["args"]["name"] for e in events
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert tracks == {0: "host leg", 1: "device leg", 3: "src-fs-0"}
    host = [e["name"] for e in events if e["ph"] == "B" and e["tid"] == 0]
    assert host == ["bare", "tick 2", "tick.drain 2", "tick.host 2",
                    "inside"]
    dev = [e["name"] for e in events if e["ph"] == "B" and e["tid"] == 1]
    assert dev == ["bridge.leg 2", "bridge.leg 3"]
    waits = [e for e in events if e["ph"] in ("b", "e")
             and e["cat"] == "bridge"]
    assert [e["name"] for e in waits if e["ph"] == "b"] == \
        ["bridge.wait 2", "bridge.wait 3"]
    (conn,) = [e for e in events if e["ph"] == "B" and e["tid"] == 3]
    assert conn["name"] == "connector.pass 7"
    assert conn["args"]["listed"] == 9
    leg2 = next(e for e in events if e["ph"] == "B"
                and e["name"] == "bridge.leg 2")
    assert leg2["args"]["queue_wait_ms"] == pytest.approx(10.0)


def _self_time(parent, children):
    """A span's duration minus the part its children cover."""
    return (parent[2] - parent[1]) - sum(
        min(c[2], parent[2]) - max(c[1], parent[1]) for c in children)


@pytest.fixture
def live_rag(monkeypatch, tmp_path):
    """A live-RAG server on this process's threads with the recorder on:
    fs connector -> embedder -> KNN index -> REST. Yields (runtime,
    client, watched directory); stops the server afterwards."""
    import hashlib
    import socket

    import numpy as np

    from pathway_tpu.engine import streaming
    from pathway_tpu.stdlib.indexing import (
        default_brute_force_knn_document_index)
    from pathway_tpu.xpacks.llm.vector_store import (VectorStoreClient,
                                                     VectorStoreServer)

    monkeypatch.setenv("PATHWAY_DEVICE_INFLIGHT", "2")

    @pw.udf
    def embed(text: str) -> np.ndarray:
        vec = np.zeros(16)
        for w in str(text).lower().split():
            vec[int(hashlib.md5(w.encode()).hexdigest(), 16) % 16] += 1.0
        n = np.linalg.norm(vec)
        return vec / n if n else vec

    watched = tmp_path / "watched"
    watched.mkdir()
    (watched / "old.txt").write_text("the quick brown fox")
    source = pw.io.fs.read(str(watched), format="plaintext_by_file",
                           mode="streaming", with_metadata=True,
                           refresh_interval_s=0.05)

    def build_index(chunks):
        # the device-resident index the benchmark serves: its operator is
        # device-bound, so every tick has a bridge leg
        return default_brute_force_knn_document_index(
            chunks.text, chunks, embedder=embed, dimensions=16,
            metadata_column=chunks.metadata)

    server = VectorStoreServer(source, embedder=embed,
                               index_builder=build_index)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    thread = server.run_server(host="127.0.0.1", port=port, threaded=True,
                               with_cache=False,
                               trace_path=str(tmp_path / "flight.json"))
    client = VectorStoreClient("127.0.0.1", port, timeout=30)
    deadline = time.monotonic() + 30
    while True:
        try:
            if client.get_vectorstore_statistics()["file_count"]:
                break
        except OSError:
            pass
        assert time.monotonic() < deadline, "the server did not come up"
        time.sleep(0.05)
    (runtime,) = streaming.live_runtimes()
    try:
        yield runtime, client, watched
    finally:
        streaming.stop_all()
        thread.join(15.0)


def test_streaming_run_records_one_chain_of_spans(live_rag):
    """One REST query and one new file: request -> tick -> leg ->
    operator steps is one chain by identifier, the connector's pass names
    the file's write and push instants, and the commit stamp derived from
    them lies before the first answer that returns the file."""
    import os

    runtime, client, watched = live_rag
    rec = runtime.recorder
    assert all(session.recorder is rec
               for _n, session, _d in runtime.sessions)

    new = watched / "new.txt"
    new.write_text("systolic arrays multiply matrices")
    first_seen = None
    deadline = time.monotonic() + 30
    while first_seen is None:
        hits = client.query("systolic arrays multiply", k=1)
        if hits and hits[0]["metadata"]["path"].endswith("new.txt"):
            first_seen = time.perf_counter()
        assert time.monotonic() < deadline, "the new file never surfaced"
    # the answer leaves from inside the leg; its spans are written when the
    # leg has retired
    while runtime.scheduler.bridge_depth():
        assert time.monotonic() < deadline, "the leg never retired"
        time.sleep(0.005)
    spans = rec.spans()
    by_name: dict = {}
    for sp in spans:
        by_name.setdefault(sp[0], {}).setdefault(sp[3], sp)

    # -- request -> tick -> leg -> operator steps ---------------------------
    request = [r for r in rec.requests.trace_spans()
               if r["route"] == "/v1/retrieve"][-1]
    cause = ("tick", request["tick"])
    tick = by_name["tick"][cause]
    drain, host = by_name["tick.drain"][cause], by_name["tick.host"][cause]
    wait, leg = by_name["bridge.wait"][cause], by_name["bridge.leg"][cause]
    assert tick[5]["requests"] >= 1 and tick[5]["rows"] >= 1
    assert any(k.startswith("rest-") for k in drain[5])
    assert tick[1] <= drain[1] <= drain[2] <= host[1] <= host[2] <= tick[2]
    assert _self_time(tick, [drain, host]) >= 0.0
    assert wait[2] == leg[1] and wait[1] <= wait[2] <= leg[2]
    assert host[1] <= wait[1] <= host[2]   # submitted inside run_time
    assert wait[5]["depth"] >= 1
    steps = [ev for ev in rec.tail_events(None) if ev[0] == request["tick"]]
    device_steps = [ev for ev in steps if ev[2] == "device"]
    assert device_steps, "the request's tick recorded no device step"
    for _t, _op, _leg, t0, dur_ms, _ri, _ro in device_steps:
        assert leg[1] <= t0 and t0 + dur_ms / 1e3 <= leg[2] + 1e-6
    # the tracker's stamps fall where the spans say
    assert tick[1] <= request["stamps"][3] <= tick[2]     # picked up
    assert request["stamps"][5] <= leg[2] + 1e-6          # resolved
    # written on the threads that did the work
    assert tick[4] != leg[4] and wait[4] == leg[4]

    # -- the connector's pass and the commit stamp ---------------------------
    mtime = os.stat(new).st_mtime
    passes = [sp for sp in spans if sp[0] == "connector.pass"]
    assert passes and passes[0][4] not in (tick[4], leg[4])
    assert [sp[3][2] for sp in passes] == list(range(len(passes)))
    (found,) = [sp for sp in passes
                if any(m == mtime for m, _t in sp[5].get("files", ()))]
    (fs_source,) = [ds for _n, _s, ds in runtime.sessions
                    if ds.name == "fs"]
    assert found[3][:2] == ("pass", fs_source._uid)
    counts = found[5]
    # the watched folder's event names the one new file: the pass examines
    # that path alone (a polling pass lists both files:
    # tests/test_io_extra.py::test_a_watch_pass_counts_the_paths_it_examined)
    assert counts["trigger"] == "watch"
    assert counts["listed"] == 1 and counts["changed"] == 1
    assert counts["rows"] == 1 and counts["list_ms"] >= 0.0
    ((_m, push),) = [f for f in counts["files"] if f[0] == mtime]
    assert found[1] <= push <= found[2]
    push_wall = push + rec._wall_ns_offset / 1e9
    assert push_wall >= mtime
    # the commit stamp: the end of the leg of the first tick that drained
    # the file's source at or after the push (a tick that a request woke
    # drains the serving sources alone: its ``tick.drain`` names no other)
    drains = sorted((sp for sp in spans if sp[0] == "tick.drain"
                     and sp[1] >= push
                     and any(k.startswith("fs-") for k in sp[5])),
                    key=lambda sp: sp[1])
    commit = by_name["bridge.leg"][drains[0][3]][2]
    assert push <= commit <= first_seen
    assert by_name["tick"][drains[0][3]][5]["woken_by"] == "period"
    assert tick[5]["woken_by"] in ("request", "period")

    # -- the surfaces ----------------------------------------------------------
    payload = rec.trace_payload()
    assert {s["name"] for s in payload["spans"]} >= {
        "tick", "tick.drain", "tick.host", "bridge.wait", "bridge.leg",
        "connector.pass"}
    assert payload["device_legs"] and all(
        set(leg_) == {"tick", "queue_wait_ms", "exec_ms"}
        for leg_ in payload["device_legs"])
    events = rec.chrome_trace_events()
    _check_nesting(events)
    tracks = {e["args"]["name"] for e in events
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"host leg", "device leg", "requests"} <= tracks
    assert any("src-fs-" in t for t in tracks)


def test_bridge_off_writes_no_bridge_spans(monkeypatch):
    """``max_inflight`` 1: no bridge, no ``bridge.*`` span, and
    ``tick.host`` covers the device work."""
    monkeypatch.setenv("PATHWAY_DEVICE_INFLIGHT", "1")
    monkeypatch.setenv("PATHWAY_FLIGHT_RECORDER", "1")
    from pathway_tpu.engine import streaming

    @pw.udf(batch=True, device=True, deterministic=True, return_type=int)
    def dev_len(ws):
        return [len(w) for w in ws]

    class Subj(pw.io.python.ConnectorSubject):
        def run(self):
            self.next(word="hello")

    t = pw.io.python.read(Subj(), schema=pw.schema_from_types(word=str),
                          autocommit_duration_ms=10)
    pw.io.subscribe(t.select(n=dev_len(t.word)), lambda *a, **k: None)
    seen: list = []
    orig = streaming.StreamingRuntime.run

    def run(self):
        seen.append(self.recorder)
        return orig(self)

    monkeypatch.setattr(streaming.StreamingRuntime, "run", run)
    pw.run()
    (rec,) = seen
    names = {sp[0] for sp in rec.spans()}
    assert "tick" in names and "tick.host" in names
    assert not {n for n in names if n.startswith("bridge.")}
    (host,) = [sp for sp in rec.spans() if sp[0] == "tick.host"
               and any(ev[0] == sp[3][1] and ev[2] == "device"
                       for ev in rec.tail_events(None))]
    step = next(ev for ev in rec.tail_events(None) if ev[2] == "device")
    assert host[1] <= step[3] and step[3] + step[4] / 1e3 <= host[2] + 1e-6
    assert rec.trace_payload()["device_legs"] == []


def test_recorder_off_session_has_no_recorder_and_pass_reads_no_clock(
        monkeypatch, tmp_path):
    """Off is free: ``Session.recorder`` stays None, and the fs source's
    pass reads ``perf_counter`` only while a recorder is on (the guard is
    tests/trace_canary.py's, which CI also runs whole)."""
    from pathway_tpu.io._datasource import Session
    from tests.trace_canary import fs_pass_clock_reads

    assert Session().recorder is None
    monkeypatch.setenv("PATHWAY_FLIGHT_RECORDER", "0")
    from pathway_tpu.engine.streaming import StreamingRuntime
    from pathway_tpu.internals.runner import GraphRunner

    t = pw.io.fs.read(str(tmp_path), format="plaintext_by_file",
                      mode="streaming")
    runner = GraphRunner()
    runner.capture(t)
    rt = StreamingRuntime(runner)
    try:
        assert rt.recorder is None
        assert rt.sessions and all(s.recorder is None
                                   for _n, s, _d in rt.sessions)
    finally:
        rt.scheduler.close()
    assert fs_pass_clock_reads(tmp_path, recording=False) == 0
    assert fs_pass_clock_reads(tmp_path, recording=True) > 0


# ---------------------------------------------------------------------------
# the reducers' re-derivation counter rides the per-operator stats
# ---------------------------------------------------------------------------

def _statistics_like_run(tmp_path, restore=None):
    """One group holding every row (count, max, tuple over ``apply``
    expressions, so the row-path ``GroupByOperator`` runs): eight
    insert-only ticks of ten rows. Returns (scheduler, recorder)."""
    from pathway_tpu.debug import table_from_rows
    from pathway_tpu.engine.graph import Scheduler
    from pathway_tpu.engine.delta import Delta
    from pathway_tpu.internals import schema as sch
    from pathway_tpu.internals.runner import GraphRunner

    G.clear()
    rows = [(f"doc{i:03d}", 1000 + i, 2 * (i // 10), 1) for i in range(80)]
    t = table_from_rows(sch.schema_from_types(name=str, at=int), rows,
                        is_stream=True)
    stats = t.reduce(
        count=pw.reducers.count(),
        newest=pw.reducers.max(pw.apply_with_type(lambda v: v, int, t.at)),
        names=pw.reducers.tuple(
            pw.apply_with_type(lambda s: s, str, t.name)))
    runner = GraphRunner()
    cap = runner.capture(stats)
    rec = FlightRecorder(trace_path=str(tmp_path / "trace.json"))
    rec.enabled = True
    sched = Scheduler(runner.graph, n_workers=1, recorder=rec)
    if restore is not None:
        sched.restore_operator_states(restore)
    by_time, times = runner.static_feeds_by_time()
    for tick in sorted(times):
        for node, groups in by_time:
            if groups.get(tick):
                sched.push_source(node, Delta(groups[tick]))
        sched.run_time(tick)
    sched.close()
    return sched, rec, cap, runner


def _groupby_stats(rec):
    [st] = [st for st in rec.op_stats() if st["op_class"] == "GroupByOperator"]
    return st


def test_rederived_counter_on_metrics_and_in_the_trace_file(tmp_path):
    from pathway_tpu.engine.http_server import MonitoringHttpServer
    from pathway_tpu.engine.operators import GroupByOperator

    sched, rec, cap, runner_ = _statistics_like_run(tmp_path)
    [(count, newest, names)] = cap.snapshot().values()
    assert (count, newest, len(names)) == (80, 1079, 80)
    st = _groupby_stats(rec)
    assert st["rows_in"] == 80
    # insert-only ticks past _ORDER_FROM entries: nothing walked
    assert st["rederived"] == 0

    def metric():
        class _Runtime:
            scheduler = sched
            runner = runner_
            sessions = ()

        server = MonitoringHttpServer(_Runtime(), port=0)
        server.start()
        try:
            import urllib.request

            text = urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics").read().decode()
        finally:
            server.stop()
        assert "# TYPE pathway_tpu_operator_reducer_rederived counter" in text
        [line] = [ln for ln in text.splitlines()
                  if ln.startswith("pathway_tpu_operator_reducer_rederived{")
                  and 'operator="groupby:' in ln]
        return int(line.rsplit(" ", 1)[1])

    def in_trace_file():
        data = json.loads(open(rec.write_chrome_trace()).read())
        [op] = [op for op in data["pathway_operators"]
                if op["name"].startswith("groupby:")]
        assert {"rows_in", "rows_out", "rederived"} <= set(op)
        return op["rederived"]

    assert metric() == 0 and in_trace_file() == 0

    # a restore derives the order of the `max` and the `tuple` state anew
    snapshot = sched.snapshot_operator_states()
    sched, rec, cap, runner_ = _statistics_like_run(tmp_path,
                                                    restore=snapshot)
    [op] = [op for reps in sched._replicas.values() for op in reps
            if isinstance(op, GroupByOperator)]
    assert op.take_rederived() == 0          # the recorder took them
    assert _groupby_stats(rec)["rederived"] == 2
    assert metric() == 2 and in_trace_file() == 2


# ---------------------------------------------------------------------------
# the stages inside a leg: a search, an ingest call, the packer (ops/knn.py,
# xpacks/llm/embedders.py through ``live_span``)
# ---------------------------------------------------------------------------

def _inside(child, parent) -> bool:
    return parent[1] <= child[1] <= child[2] <= parent[2]


@pytest.fixture
def live_text_rag(monkeypatch, tmp_path):
    """``live_rag`` with the embedder inside the index, as the benchmark
    serves it: the index takes text, a query's text is packed and embedded
    inside its search. Yields (runtime, client, watched directory)."""
    import socket

    from pathway_tpu.engine import streaming
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.ops.knn import KnnMetric
    from pathway_tpu.stdlib.indexing import (
        default_brute_force_knn_document_index)
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder
    from pathway_tpu.xpacks.llm.vector_store import (VectorStoreClient,
                                                     VectorStoreServer)

    monkeypatch.setenv("PATHWAY_DEVICE_INFLIGHT", "2")
    emb = JaxEncoderEmbedder(config=EncoderConfig.tiny(), ragged=True,
                             max_len=64)
    watched = tmp_path / "watched"
    watched.mkdir()
    (watched / "old.txt").write_text("the quick brown fox")
    source = pw.io.fs.read(str(watched), format="plaintext_by_file",
                           mode="streaming", with_metadata=True,
                           refresh_interval_s=0.05)

    def build_index(chunks):
        return default_brute_force_knn_document_index(
            chunks.text, chunks, embedder=emb,
            dimensions=emb.get_embedding_dimension(),
            metadata_column=chunks.metadata, metric=KnnMetric.COS)

    server = VectorStoreServer(source, embedder=emb,
                               index_builder=build_index)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    thread = server.run_server(host="127.0.0.1", port=port, threaded=True,
                               with_cache=False,
                               trace_path=str(tmp_path / "flight.json"))
    client = VectorStoreClient("127.0.0.1", port, timeout=60)
    deadline = time.monotonic() + 60
    while True:
        try:
            if client.get_vectorstore_statistics()["file_count"]:
                break
        except OSError:
            pass
        assert time.monotonic() < deadline, "the server did not come up"
        time.sleep(0.05)
    (runtime,) = streaming.live_runtimes()
    try:
        yield runtime, client, watched
    finally:
        streaming.stop_all()
        thread.join(15.0)


def test_a_search_and_an_ingest_call_are_chains_inside_their_legs(
        live_text_rag):
    """One new file and one query: request -> tick -> ``bridge.leg`` ->
    ``index.search`` -> ``search.embed`` -> ``embedder.pack`` ->
    ``embedder.tokenize`` is one chain by identifier and by containment,
    and so is the file's ``index.add_batch`` in the leg of its tick."""
    runtime, client, watched = live_text_rag
    rec = runtime.recorder
    (watched / "new.txt").write_text("systolic arrays multiply matrices")
    deadline = time.monotonic() + 60
    while True:
        hits = client.query("systolic arrays multiply matrices", k=1)
        if hits and hits[0]["metadata"]["path"].endswith("new.txt"):
            break
        assert time.monotonic() < deadline, "the new file never surfaced"
    while runtime.scheduler.bridge_depth():
        assert time.monotonic() < deadline, "the leg never retired"
        time.sleep(0.005)
    spans = rec.spans()
    legs = {sp[3]: sp for sp in spans if sp[0] == "bridge.leg"}

    def of(cause) -> dict:
        out: dict = {}
        for sp in spans:
            if sp[3] == cause:
                out.setdefault(sp[0], []).append(sp)
        return out

    # -- the last query ------------------------------------------------------
    request = [r for r in rec.requests.trace_spans()
               if r["route"] == "/v1/retrieve"][-1]
    cause = ("tick", request["tick"])
    mine = of(cause)
    (search,), (embed,), (scan,) = (mine["index.search"],
                                    mine["search.embed"],
                                    mine["search.scan"])
    # the file is pushed as it lands, so its ingest call may share the
    # query's tick: the query's own pack is the one inside its embed
    (pack,) = [sp for sp in mine["embedder.pack"] if _inside(sp, embed)]
    (tokenize,) = [sp for sp in mine["embedder.tokenize"]
                   if _inside(sp, pack)]
    leg = legs[cause]
    assert _inside(search, leg)
    assert _inside(embed, search) and _inside(scan, search)
    assert _inside(pack, embed) and _inside(tokenize, pack)
    assert embed[2] <= scan[1]
    assert search[5]["queries"] == embed[5]["queries"] \
        == scan[5]["queries"] == tick_requests(mine) >= 1
    assert search[5]["rounds"] == 1 and scan[5]["extents"] == 1
    assert pack[5]["texts"] == tokenize[5]["texts"] == search[5]["queries"]
    # written on the bridge worker's thread, the leg's
    assert {sp[4] for sp in (search, embed, scan, pack, tokenize)} \
        == {leg[4]}
    assert _self_time(search, [embed, scan]) >= 0.0
    assert _self_time(leg, [search]) >= 0.0

    # -- the new file's ingest call --------------------------------------------
    adds = [sp for sp in spans if sp[0] == "index.add_batch"]
    assert len(adds) == 2 and [sp[5]["docs"] for sp in adds] == [1, 1]
    add = adds[-1]
    assert add[5] == {"docs": 1, "dispatches": 1, "fused": 1}
    assert add[3] in legs and _inside(add, legs[add[3]])
    its = of(add[3])
    (dispatch,) = its["embedder.dispatch"]
    (pack,) = [sp for sp in its["embedder.pack"] if _inside(sp, add)]
    (tokenize,) = [sp for sp in its["embedder.tokenize"]
                   if _inside(sp, pack)]
    assert _inside(dispatch, add) and pack[2] <= dispatch[1]
    assert dispatch[5]["tokens"] == pack[5]["tokens"] == tokenize[5]["tokens"]
    # the tick that carried the file drained its source
    (drain,) = its["tick.drain"]
    assert any(k.startswith("fs-") for k in drain[5])

    # -- the surfaces: the tracks that exist, no new one -----------------------
    payload = rec.trace_payload()
    assert {s["name"] for s in payload["spans"]} >= {
        "index.search", "search.embed", "search.scan", "index.add_batch",
        "embedder.pack", "embedder.tokenize", "embedder.dispatch"}
    events = rec.chrome_trace_events()
    _check_nesting(events)
    tracks = {e["tid"]: e["args"]["name"] for e in events
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert tracks[0] == "host leg" and tracks[1] == "device leg"
    assert all("src-" in name or name == "requests"
               for tid, name in tracks.items() if tid > 1), tracks
    staged = [e for e in events if e["ph"] == "B" and e["name"].split()[0]
              in ("index.search", "search.embed", "search.scan",
                  "index.add_batch", "embedder.pack", "embedder.tokenize",
                  "embedder.dispatch")]
    assert staged and {e["tid"] for e in staged} == {1}


def tick_requests(spans_of_tick: dict) -> int:
    (tick,) = spans_of_tick["tick"]
    return tick[5]["requests"]


def test_chrome_export_nests_the_stages_under_their_leg_and_their_pass():
    """The stages lie on the track of the thread that wrote them, nested by
    interval: under ``bridge.leg`` on the device-leg track (under
    ``tick.host`` on the host's with the bridge off), ``connector.progress``
    under its ``connector.pass``."""
    rec = FlightRecorder()
    rec.enabled = True
    rec._epoch = 0.0
    # both alive at once: a thread's identifier is free again when it ends
    both, done = threading.Barrier(2), threading.Event()

    def bridge():
        both.wait(5.0)
        rec.span("bridge.wait", 1.05, 1.06, ("tick", 2), depth=1)
        rec.span("embedder.tokenize", 1.061, 1.062, ("tick", 2), texts=1)
        rec.span("embedder.pack", 1.061, 1.063, ("tick", 2), texts=1)
        rec.span("search.embed", 1.061, 1.07, ("tick", 2), queries=1)
        rec.span("search.scan", 1.071, 1.09, ("tick", 2), queries=1)
        rec.span("index.search", 1.061, 1.095, ("tick", 2), queries=1)
        rec.span("bridge.leg", 1.06, 1.10, ("tick", 2))
        done.set()

    def reader():
        both.wait(5.0)
        done.wait(5.0)
        rec.span("connector.progress", 0.6, 0.9, ("pass", 0, 7), files=256)
        rec.span("connector.progress", 0.9, 1.4, ("pass", 0, 7), files=44)
        rec.span("connector.pass", 0.5, 1.4, ("pass", 0, 7), listed=300)

    # bridge off: the same stages on the commit loop's thread
    rec.span("tick", 2.00, 2.05, ("tick", 3), rows=1, requests=1)
    rec.span("search.scan", 2.021, 2.04, None, queries=1)
    rec.span("index.search", 2.02, 2.045, None, queries=1)
    rec.span("tick.host", 2.01, 2.05, ("tick", 3))
    threads = [threading.Thread(target=bridge, name="device-bridge"),
               threading.Thread(target=reader, name="src-fs-0")]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    events = rec.chrome_trace_events()
    _check_nesting(events)
    tracks = {e["tid"]: e["args"]["name"] for e in events
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert tracks == {0: "host leg", 1: "device leg", 3: "src-fs-0"}
    begun = {tid: [e["name"] for e in events if e["ph"] == "B"
                   and e["tid"] == tid] for tid in tracks}
    assert begun[1] == ["bridge.leg 2", "index.search 2", "search.embed 2",
                        "embedder.pack 2", "embedder.tokenize 2",
                        "search.scan 2"]
    assert begun[0] == ["tick 3", "tick.host 3", "index.search",
                        "search.scan"]
    assert begun[3] == ["connector.pass 7", "connector.progress 7",
                        "connector.progress 7"]


def test_the_span_store_is_as_large_as_the_operator_ring():
    from pathway_tpu.engine.flight_recorder import (_DEFAULT_BUFFER_EVENTS,
                                                    _SPAN_BUFFER)

    assert _SPAN_BUFFER == _DEFAULT_BUFFER_EVENTS == 65_536
    rec = FlightRecorder()
    assert rec._spans.maxlen == _SPAN_BUFFER
