"""The readers of the program's own spans: each on a hand-made run (known
spans, known device intervals, a known clock offset) against the value
worked out by hand, None where its input is missing, and one traced CPU
rehearsal of each cell that must print every one of them. Nothing here is
a speed."""

from __future__ import annotations

import time
import types

import pytest

from benchmark.lib import program_spans, runner, spec
from benchmark.lib.record import Run
from benchmark.lib.trace import DeviceTrace, Reduced

from conftest import FAKE_PEAKS, ROOT, tiny_cell

QUERY = "bge-small-10m.query-steady"
INGEST = "bge-small-10m.ingest-backlog"
NEW = {
    QUERY: {"query.tick_period_ms_p50", "query.tick_busy_ms_p50",
            "request.bridge_wait_ms_p50", "request.device_busy_ms_p50",
            "request.leg_host_ms_p50", "query.idle_in_tick_wait_share",
            "documents.commit_ms_p50", "connector.lag_ms_p50",
            "connector.pass_ms_p50"},
    INGEST: {"ingest.tick_busy_share", "ingest.idle_in_tick_wait_share",
             "ingest.top_operator_share", "ingest.tick_ms_per_row_drift"},
}

#: the profile's clock minus perf_counter, and wall time minus perf_counter
OFFSET_S, WALL_S = 1000.0, 5000.0


class _Node:
    def __init__(self, id, name):
        self.id, self.name, self.op, self.trace = id, name, object(), None


def _recorder():
    """A window 100..130 s with five ticks, three connector passes and
    two operators; ticks 3 and 4 fall into the traced part 110..115 s."""
    from pathway_tpu.engine.flight_recorder import FlightRecorder

    rec = FlightRecorder()
    rec.enabled = True
    rec._wall_ns_offset = int(WALL_S * 1e9)
    tick = lambda n: ("tick", n)  # noqa: E731
    rec.span("tick", 99.0, 99.5, tick(0), rows=9, requests=9)  # before w0
    rec.span("tick", 100.00, 100.01, tick(1), rows=0, requests=0)
    rec.span("tick", 100.06, 100.08, tick(2), rows=2, requests=1)
    rec.span("tick.drain", 100.061, 100.063, tick(2),
             **{"fs-0": 1, "rest-1": 1})
    rec.span("tick.host", 100.063, 100.08, tick(2))
    rec.span("bridge.wait", 100.079, 100.081, tick(2), depth=1)
    rec.span("bridge.leg", 100.081, 100.101, tick(2))
    rec.span("tick", 110.000, 110.004, tick(3), rows=2, requests=2)
    rec.span("tick.drain", 110.001, 110.002, tick(3), **{"rest-1": 2})
    rec.span("bridge.wait", 110.003, 110.005, tick(3), depth=1)
    rec.span("bridge.leg", 110.005, 110.035, tick(3))
    rec.span("tick", 110.050, 110.056, tick(4), rows=2, requests=1)
    rec.span("tick.drain", 110.051, 110.052, tick(4),
             **{"fs-0": 1, "rest-1": 1})
    rec.span("bridge.wait", 110.055, 110.056, tick(4), depth=1)
    rec.span("bridge.leg", 110.056, 110.076, tick(4))
    rec.span("tick", 129.00, 129.03, tick(5), rows=4, requests=0)
    rec.span("bridge.leg", 129.03, 129.20, tick(5))
    # (st_mtime in wall seconds, push instant) per file
    rec.span("connector.pass", 100.5, 101.1, ("pass", 0, 0), listed=3,
             changed=1, rows=1, list_ms=400.0,
             files=[(WALL_S + 100.4, 100.9)])
    rec.span("connector.pass", 109.9, 110.045, ("pass", 0, 1), listed=4,
             changed=1, rows=1, list_ms=100.0,
             files=[(WALL_S + 109.74, 110.04)])
    rec.span("connector.pass", 129.8, 130.4, ("pass", 0, 2), listed=5,
             changed=1, rows=1, list_ms=100.0,
             files=[(WALL_S + 129.0, 130.1)])   # pushed after the window
    groupby, index = _Node(0, "groupby"), _Node(1, "index")
    rec.record(0, index, "device", 98.9, 1000.0, 1, 1)   # before w0
    for t0 in (100.0, 110.0, 129.0):
        rec.record(1, groupby, "host", t0, 100.0, 1, 1)
    rec.record(3, index, "device", 110.01, 600.0, 1, 1)
    return rec


def _run(recorder=None, traced=True, requests=True) -> Run:
    run = Run(cell=None, t_start=0.0, w0=100.0, w1=130.0, before={},
              after={}, jit=None)
    if recorder is not None:
        run.extras["system"] = types.SimpleNamespace(
            runtime=types.SimpleNamespace(recorder=recorder))
    if requests:
        run.requests = [{"tick": 3}, {"tick": 3}, {"tick": 4}, {"tick": 2}]
    if traced:
        # busy 110.010-110.022 and 110.060-110.070 on perf_counter
        gaps = [(1110.0, 1110.010), (1110.022, 1110.060), (1110.070, 1115.0)]
        run.trace = Reduced(1110.0, 1115.0, [DeviceTrace(
            "/device:TPU:0", 0.022, {}, {}, gaps)], [], OFFSET_S)
        run.traced = (110.0, 115.0)
    return run


@pytest.mark.parametrize("read, expected", [
    # starts 100.00, 100.06, 110.00, 110.05, 129.00 -> 60, 9940, 50, 18950
    (program_spans.tick_period_ms_p50, (60.0 + 9940.0) / 2),
    # ticks 2, 3, 4 picked up requests: 20, 4, 6 ms
    (program_spans.tick_busy_ms_p50, 6.0),
    # requests of ticks 3, 3, 4, 2 waited 2, 2, 1, 2 ms
    (program_spans.bridge_wait_ms_p50, 2.0),
    # leg 3 (30 ms) holds 12 ms of busy chip, leg 4 (20 ms) 10 ms; the
    # request of tick 2 lies outside the traced part: [12, 12, 10]
    (program_spans.device_busy_ms_p50, 12.0),
    (program_spans.leg_host_ms_p50, 18.0),
    # idle 10 + 38 + 4930 ms; of it outside ticks 3, 4 and their legs
    # 1 + 15 + 4924 ms
    (program_spans.idle_in_tick_wait_share, 100.0 * 4.940 / 4.978),
    # pushed 100.9 -> leg of tick 3 ends 110.035; 110.04 -> tick 4, 110.076
    (program_spans.commit_ms_p50, (9135.0 + 36.0) / 2),
    # pushed 500 and 300 ms after their mtime
    (program_spans.connector_lag_ms_p50, 400.0),
    # passes of 600 and 145 ms; the third ends after the window
    (program_spans.pass_ms_p50, 372.5),
    # 10 + 20 + 4 + 6 + 30 ms of 30 s
    (program_spans.tick_busy_share, 100.0 * 0.070 / 30.0),
    # first third: (10 + 20 + leg 20) ms over 2 rows; last: (30 + 170) over 4
    (program_spans.tick_ms_per_row_drift, 2.0),
])
def test_a_reader_returns_the_hand_computed_value(read, expected):
    assert read(_run(_recorder())) == pytest.approx(expected, rel=1e-6)


def test_operator_shares_name_the_costliest_first():
    shares = program_spans.operator_shares(_run(_recorder()))
    assert [name for name, _s in shares] == ["index", "groupby"]
    # 600 ms and 3 x 100 ms of a 30 s window
    assert [s for _n, s in shares] == pytest.approx([2.0, 1.0])


ALL_READERS = [
    program_spans.tick_period_ms_p50, program_spans.tick_busy_ms_p50,
    program_spans.bridge_wait_ms_p50, program_spans.device_busy_ms_p50,
    program_spans.leg_host_ms_p50, program_spans.idle_in_tick_wait_share,
    program_spans.commit_ms_p50, program_spans.connector_lag_ms_p50,
    program_spans.pass_ms_p50, program_spans.tick_busy_share,
    program_spans.tick_ms_per_row_drift, program_spans.operator_shares]


@pytest.mark.parametrize("read", ALL_READERS)
def test_a_reader_returns_none_without_a_span_store(read):
    # an untraced run has no system's recorder; a program from before the
    # span store has a recorder that keeps no spans; an idle one none yet
    from pathway_tpu.engine.flight_recorder import FlightRecorder

    assert read(_run(None)) is None
    assert read(_run(object())) is None
    assert not read(_run(FlightRecorder()))


@pytest.mark.parametrize("read", [
    program_spans.device_busy_ms_p50, program_spans.leg_host_ms_p50,
    program_spans.idle_in_tick_wait_share])
def test_a_device_reader_needs_the_profile_and_its_clock(read):
    assert read(_run(_recorder(), traced=False)) is None
    run = _run(_recorder())
    run.trace.clock_offset_s = None
    assert read(run) is None


@pytest.mark.parametrize("read", [
    program_spans.bridge_wait_ms_p50, program_spans.device_busy_ms_p50,
    program_spans.leg_host_ms_p50])
def test_a_request_reader_needs_requests(read):
    assert read(_run(_recorder(), requests=False)) is None


def test_the_commit_stamp_falls_back_to_the_host_leg_with_the_bridge_off():
    from pathway_tpu.engine.flight_recorder import FlightRecorder

    rec = FlightRecorder()
    rec.enabled = True
    rec.span("connector.pass", 100.5, 101.1, ("pass", 0, 0),
             files=[(WALL_S + 100.4, 100.9)])
    rec.span("tick.drain", 101.0, 101.001, ("tick", 7), **{"fs-0": 1})
    rec.span("tick.host", 101.001, 101.05, ("tick", 7))
    assert program_spans.commit_ms_p50(_run(rec)) == pytest.approx(150.0)


def test_the_new_entries_resolve_to_their_readers():
    loaded = spec.load(ROOT)
    for cell, names in NEW.items():
        layers = {m.name: m for m in loaded.cell(cell).layers}
        assert names <= set(layers)
        for name in names:
            assert layers[name].read(_run(None)) is None
    layer_of = {m["name"]: m for m in loaded.benchmark["per_layer"]}
    known = {m["layer"] for m in loaded.benchmark["per_layer"][:30]}
    for name in NEW[QUERY] | NEW[INGEST]:
        assert layer_of[name]["layer"] in known
        assert layer_of[name]["source"] in ("program_span", "device_trace")


@pytest.mark.parametrize("workload, seconds", [(QUERY, 4), (INGEST, 3)])
def test_a_traced_rehearsal_prints_every_new_metric(workload, seconds,
                                                    tmp_path, capsys):
    cell = tiny_cell(workload)
    line = runner.run_cell(cell, seed=5, seconds=seconds, trace=True,
                           expected_platform="cpu",
                           t_start=time.perf_counter(),
                           out_dir=str(tmp_path), peaks=FAKE_PEAKS)
    got = line["metrics"]
    assert NEW[workload] <= set(got), NEW[workload] - set(got)
    for name in NEW[workload]:
        assert isinstance(got[name]["value"], float)
    if workload == QUERY:
        # the stages nest: a leg holds the chip's busy time inside it, the
        # tracker's device stage holds the wait and the leg
        leg = got["request.device_busy_ms_p50"]["value"] \
            + got["request.leg_host_ms_p50"]["value"]
        assert 0 <= got["request.device_busy_ms_p50"]["value"] <= leg
        assert got["query.tick_period_ms_p50"]["value"] >= 50.0
        assert 0 <= got["query.idle_in_tick_wait_share"]["value"] <= 100
        assert got["documents.commit_ms_p50"]["value"] > 0
        assert got["connector.lag_ms_p50"]["value"] >= 0
    else:
        assert 0 < got["ingest.tick_busy_share"]["value"] <= 100
        assert 0 < got["ingest.top_operator_share"]["value"] <= 100
        assert got["ingest.tick_ms_per_row_drift"]["value"] > 0
        assert "ingest.top_operator_share: " in capsys.readouterr().out
