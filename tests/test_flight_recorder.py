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
    track, with leg-level queue-wait/exec metadata on the tick wrapper."""
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
    wrappers = [e for e in dev_b if e["name"].startswith("tick ")
                and "queue_wait_ms" in e["args"]]
    assert wrappers, "device tick wrappers carry no queue-wait attribution"
    assert any(e["name"].startswith("map:") for e in dev_b)


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
