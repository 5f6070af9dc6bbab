"""The readers of the stages the program records inside a search, an ingest
call and a connector's pass: each of the fourteen on a hand-made run against
the value worked out by hand, None where its input is missing (no recorder,
no such span, the window flushed out of the store), and one traced CPU
rehearsal of each cell that must print every one listed for it. Nothing here
is a speed."""

from __future__ import annotations

import functools
import time
import types

import pytest

from benchmark.lib import runner, spec
from benchmark.lib.record import Run
from benchmark.lib.trace import DeviceTrace, Reduced

from conftest import FAKE_PEAKS, ROOT, tiny_cell

QUERY = "bge-small-10m.query-steady"
BACKLOG = "bge-small-10m.ingest-backlog"
DECODERS = {"qwen3-next-a3b-embed.ingest-chunks": ("test_qwen3_next", 16),
            "smallthinker-21b-embed.ingest-long-mixed":
                ("test_smallthinker", 24),
            "longcat-flash-embed.ingest-sections":
                ("test_longcat_flash", 24)}
SEARCH = {"search.embed_ms_p50", "search.embed_host_ms_p50",
          "search.scan_ms_p50", "search.scan_host_ms_p50",
          "search.scan_dispatch_ms_p50", "search.self_ms_p50",
          "request.leg_outside_search_ms_p50", "connector.pass_cpu_share"}
SHARES = {"ingest.index_add_batch_share", "ingest.embedder_pack_share",
          "ingest.embedder_tokenize_share"}
PROGRESS = {"connector.file_ms_mean", "connector.reader_cpu_share",
            "connector.parse_share"}
LISTED = {QUERY: SEARCH, BACKLOG: SHARES | PROGRESS,
          **{cell: SHARES for cell in DECODERS}}

#: the profile's clock minus perf_counter
OFFSET_S = 1000.0


def _recorder(first: float = 99.0):
    """A window 100..130 s. Three legs that served requests, each holding
    one search (ticks 3 and 4 in the traced part 110..115 s, tick 2 before
    it), four ingest calls of which two straddle the window's edges, three
    passes and three progress spans of which the last of each ends after
    the window. ``first``: where the store's oldest span starts."""
    from pathway_tpu.engine.flight_recorder import FlightRecorder

    rec = FlightRecorder()
    rec.enabled = True
    rec.span("tick", first, first + 0.5, ("tick", 0), rows=9, requests=9)

    def search(tick, leg, embed, scan, dispatch_ms):
        cause = ("tick", tick)
        rec.span("bridge.wait", leg[0] - 0.002, leg[0], cause, depth=1)
        rec.span("search.embed", *embed, cause, queries=1)
        rec.span("search.scan", *scan, cause, queries=1, fetch_k=3,
                 extents=1, dispatch_ms=dispatch_ms)
        rec.span("index.search", embed[0], leg[1] - 0.001, cause, queries=1,
                 flush_rows=0, prepare_ms=0.2, rank_ms=0.1, rounds=1)
        rec.span("bridge.leg", *leg, cause)

    # leg 20 ms: search 18 = embed 3 + scan 13 + self 2
    search(2, (100.081, 100.101), (100.082, 100.085), (100.086, 100.099),
           0.4)
    # leg 30 ms: search 28 = embed 4 + scan 20 + self 4; its packer's spans
    rec.span("embedder.tokenize", 110.006, 110.0065, ("tick", 3), texts=1,
             tokens=5)
    rec.span("embedder.pack", 110.006, 110.007, ("tick", 3), texts=1,
             rows=1, slots=48, tokens=5)
    search(3, (110.005, 110.035), (110.006, 110.010), (110.011, 110.031),
           0.5)
    # leg 20 ms: search 18 = embed 2 + scan 14 + self 2
    search(4, (110.056, 110.076), (110.057, 110.059), (110.060, 110.074),
           0.3)
    for t0, t1 in ((99.9, 100.1), (105.0, 106.0), (112.0, 112.5),
                   (129.9, 130.2)):
        rec.span("index.add_batch", t0, t1, ("tick", 9), docs=8,
                 dispatches=1, fused=1)
    for t0, t_tokens, t1 in ((105.0, 105.1, 105.3), (112.0, 112.05, 112.1)):
        rec.span("embedder.tokenize", t0, t_tokens, ("tick", 9), texts=8,
                 tokens=80)
        rec.span("embedder.pack", t0, t1, ("tick", 9), texts=8, rows=1,
                 slots=128, tokens=80)
    rec.span("connector.pass", 100.5, 101.1, ("pass", 0, 0), listed=3,
             changed=1, cpu_ms=480.0, list_ms=60.0, stat_ms=400.0,
             parse_ms=100.0, push_ms=40.0)
    rec.span("connector.pass", 109.9, 110.045, ("pass", 0, 1), listed=4,
             changed=1, cpu_ms=116.0, list_ms=20.0, stat_ms=100.0,
             parse_ms=20.0, push_ms=5.0)
    rec.span("connector.pass", 129.8, 130.4, ("pass", 0, 2), listed=5,
             changed=1, cpu_ms=600.0, list_ms=1.0, stat_ms=1.0,
             parse_ms=1.0, push_ms=1.0)
    for t0, cpu, parse, push in ((101.0, 200.0, 150.0, 50.0),
                                 (101.25, 150.0, 125.0, 75.0),
                                 (129.9, 250.0, 1.0, 1.0)):
        rec.span("connector.progress", t0, t0 + 0.25, ("pass", 0, 3),
                 files=256, rows=256, cpu_ms=cpu, stat_ms=25.0,
                 parse_ms=parse, push_ms=push)
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
        # the chip busy, on perf_counter: 1 ms inside tick 3's embed and 12
        # inside its scan; 0.5 ms inside tick 4's embed and 10 in its scan
        busy = [(110.008, 110.009), (110.012, 110.024), (110.0575, 110.058),
                (110.061, 110.071)]
        gaps, cur = [], 110.0
        for s, e in busy:
            gaps.append((cur + OFFSET_S, s + OFFSET_S))
            cur = e
        gaps.append((cur + OFFSET_S, 115.0 + OFFSET_S))
        run.trace = Reduced(1110.0, 1115.0, [DeviceTrace(
            "/device:TPU:0", 0.0235, {}, {}, gaps)], [], OFFSET_S)
        run.traced = (110.0, 115.0)
    return run


@functools.lru_cache(maxsize=None)
def _readers() -> dict:
    return {m.name: m.read for cell in spec.load(ROOT).cells.values()
            for m in cell.layers}


def _reader(name: str):
    return _readers()[name]


#: name -> the value worked out by hand from ``_recorder``
EXPECTED = {
    # the requests' ticks 3, 3, 4, 2: embeds of 4, 4, 2, 3 ms
    "search.embed_ms_p50": 3.5,
    # in the traced part ticks 3, 3, 4: 4 - 1, 4 - 1, 2 - 0.5
    "search.embed_host_ms_p50": 3.0,
    # scans of 20, 20, 14, 13 ms
    "search.scan_ms_p50": 17.0,
    # 20 - 12, 20 - 12, 14 - 10
    "search.scan_host_ms_p50": 8.0,
    # 0.5, 0.5, 0.3, 0.4
    "search.scan_dispatch_ms_p50": 0.45,
    # searches of 28, 28, 18, 18 less embed and scan: 4, 4, 2, 2
    "search.self_ms_p50": 3.0,
    # legs of 30, 30, 20, 20 less their searches
    "request.leg_outside_search_ms_p50": 2.0,
    # the two passes inside the window: (480 + 116) of (600 + 145) ms
    "connector.pass_cpu_share": 100.0 * 596.0 / 745.0,
    # 0.1 + 1.0 + 0.5 + 0.1 s of 30
    "ingest.index_add_batch_share": 100.0 * 1.7 / 30.0,
    # packs of 0.3, 0.1 and 0.001 s less tokenizing of 0.1, 0.05, 0.0005
    "ingest.embedder_pack_share": 100.0 * (0.401 - 0.1505) / 30.0,
    "ingest.embedder_tokenize_share": 100.0 * 0.1505 / 30.0,
    # the two progress spans inside the window: 500 ms over 512 files
    "connector.file_ms_mean": 500.0 / 512.0,
    "connector.reader_cpu_share": 70.0,
    "connector.parse_share": 55.0,
}


def test_every_listed_metric_has_a_hand_computed_case():
    assert set(EXPECTED) == SEARCH | SHARES | PROGRESS
    assert len(EXPECTED) == 14


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_reader_returns_the_hand_computed_value(name):
    assert _reader(name)(_run(_recorder())) == pytest.approx(
        EXPECTED[name], rel=1e-6)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_reader_returns_none_where_there_is_nothing_to_read(name,
                                                              capsys):
    from pathway_tpu.engine.flight_recorder import FlightRecorder

    read = _reader(name)
    # an untraced run has no system's recorder; a program from before the
    # span store has a recorder that keeps no spans; an idle one none yet
    assert read(_run(None)) is None
    assert read(_run(object())) is None
    assert read(_run(FlightRecorder())) is None
    # a program from before this PR: ticks, legs and passes, none of the
    # stages, no ``cpu_ms`` on a pass
    old = FlightRecorder()
    old.enabled = True
    old.span("tick", 99.0, 99.5, ("tick", 0), rows=1, requests=1)
    old.span("bridge.wait", 110.003, 110.005, ("tick", 3), depth=1)
    old.span("bridge.leg", 110.005, 110.035, ("tick", 3))
    old.span("connector.pass", 100.5, 101.1, ("pass", 0, 0), listed=3,
             changed=1, rows=1, list_ms=400.0)
    assert read(_run(old)) is None
    # the store's oldest span starts inside the window: what came before
    # it was flushed out, and a number over the rest would mislead
    capsys.readouterr()
    assert read(_run(_recorder(first=100.001))) is None
    assert "THE WINDOW'S FIRST SPANS ARE GONE" in capsys.readouterr().out


@pytest.mark.parametrize("name", ["search.embed_host_ms_p50",
                                  "search.scan_host_ms_p50"])
def test_a_host_part_needs_the_profile_and_its_clock(name):
    read = _reader(name)
    assert read(_run(_recorder(), traced=False)) is None
    run = _run(_recorder())
    run.trace.clock_offset_s = None
    assert read(run) is None


@pytest.mark.parametrize("name", sorted(SEARCH - {"connector.pass_cpu_share"}))
def test_a_request_reader_needs_requests(name):
    assert _reader(name)(_run(_recorder(), requests=False)) is None


def test_the_store_is_read_and_reported_once_a_run(capsys):
    run = _run(_recorder())
    for name in sorted(EXPECTED):
        _reader(name)(run)
    out = capsys.readouterr().out
    assert out.count("stage_spans: the store holds") == 1
    assert "the window is whole" in out
    # the reader's progress is logged in every cell whose window holds any,
    # the ``stat`` and push shares beside the parse share
    assert out.count("stage_spans: connector.progress: 2 spans") == 1
    assert '"stat_ms": 10.0' in out and '"push_ms": 25.0' in out
    # a share is logged over the traced part too: 0.5 s of 5
    assert "index.add_batch: 5.667 % of the window, 10.000 % of its " \
        "traced part" in out


def test_the_fourteen_entries_are_appended_and_resolve():
    loaded = spec.load(ROOT)
    new = loaded.benchmark["per_layer"][-14:]
    assert {m["name"] for m in new} == set(EXPECTED)
    layers = {m["layer"] for m in loaded.benchmark["per_layer"][:-14]}
    for m in new:
        assert m["source"] == "program_span" and m["layer"] in layers
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        cells = {cell for cell, names in LISTED.items()
                 if m["name"] in names}
        assert set(m["workloads"]) == cells
        for cell in cells:
            assert m["name"] in {x.name for x in loaded.cell(cell).layers}


def _rehearse(cell, seconds, tmp_path):
    return runner.run_cell(cell, seed=5, seconds=seconds, trace=True,
                           expected_platform="cpu",
                           t_start=time.perf_counter(),
                           out_dir=str(tmp_path), peaks=FAKE_PEAKS)


@pytest.mark.parametrize("workload, seconds", [(QUERY, 4), (BACKLOG, 3)])
def test_a_traced_rehearsal_prints_every_new_metric(workload, seconds,
                                                    tmp_path, capsys):
    line = _rehearse(tiny_cell(workload), seconds, tmp_path)
    got = line["metrics"]
    assert LISTED[workload] <= set(got), LISTED[workload] - set(got)
    out = capsys.readouterr().out
    assert "the window is whole" in out
    value = {name: got[name]["value"] for name in LISTED[workload]}
    assert all(isinstance(v, float) for v in value.values())
    if workload == QUERY:
        # the stages nest: the wrapper's span encloses the program's
        # search, which encloses its embed and its scan
        assert 0 < value["search.embed_host_ms_p50"] \
            <= value["search.embed_ms_p50"] + 1e-9
        assert 0 <= value["search.scan_dispatch_ms_p50"] \
            <= value["search.scan_ms_p50"]
        assert value["search.self_ms_p50"] >= 0
        assert value["request.leg_outside_search_ms_p50"] >= 0
        assert value["search.embed_ms_p50"] + value["search.scan_ms_p50"] \
            <= got["index.search_ms_p50"]["value"] * 1.5
        assert 0 < value["connector.pass_cpu_share"] <= 110.0
    else:
        # the wrappers' twins: the same calls, seen from inside
        assert 0 < value["ingest.embedder_tokenize_share"] \
            <= value["ingest.index_add_batch_share"] <= 100.0
        assert value["ingest.embedder_pack_share"] > 0
        assert value["connector.file_ms_mean"] > 0
        assert 0 < value["connector.reader_cpu_share"] <= 110.0
        assert 0 < value["connector.parse_share"] < 100.0
        assert "stage_spans: connector.progress: " in out
        assert "% of its traced part" in out


@pytest.mark.parametrize("workload", sorted(DECODERS))
def test_a_decoder_cell_s_rehearsal_prints_its_three_shares(workload,
                                                            tmp_path):
    """The toy size is the cell's own test file's (no file of the benchmark
    that is there is edited to share it)."""
    import importlib

    module, seconds = DECODERS[workload]
    toy = importlib.import_module(module)._toy(
        spec.load(ROOT).cell(workload))
    got = _rehearse(toy, seconds, tmp_path)["metrics"]
    assert SHARES <= set(got), SHARES - set(got)
    assert not PROGRESS & set(got)
    assert 0 < got["ingest.embedder_tokenize_share"]["value"] \
        <= got["ingest.index_add_batch_share"]["value"] <= 100.0
