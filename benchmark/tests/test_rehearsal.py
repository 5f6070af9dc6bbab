"""CPU rehearsal of the cells end to end, through the function the command
runs, with the platform it must find passed as an argument — and the proof
that the command itself refuses anything but a TPU. Nothing here is a
speed."""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

import pytest

from benchmark.lib import runner

from conftest import FAKE_PEAKS, ROOT, TOY, tiny_cell, toy_cell


def _run(cell, tmp_path, **kw):
    return runner.run_cell(cell, seed=3, expected_platform="cpu",
                           t_start=time.perf_counter(),
                           out_dir=str(tmp_path), peaks=FAKE_PEAKS, **kw)


def _well_formed(line: dict, cell, traced: bool) -> None:
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["device"]["platform"] == "cpu"
    names = {m.name for m in (cell.layers if traced else cell.end_to_end)}
    assert set(line["metrics"]) <= names
    for value in line["metrics"].values():
        assert set(value) == {"value", "unit"}
        assert isinstance(value["value"], float)
    # each number compared beside its limit, last in the line
    assert list(line)[-1] == "compared"
    assert {"min_cos", "mean_cos", "requests_failed", "first_hits_wrong",
            "extents"} <= set(line["compared"])
    for pair in line["compared"].values():
        assert set(pair) == {"value", "limit"}
    assert line["compared"]["min_cos"]["limit"] == cell.reference.MIN_COS
    json.dumps(line)


def test_query_steady_untraced_then_traced(tmp_path):
    cell = tiny_cell("bge-small-10m.query-steady")
    line = _run(cell, tmp_path, seconds=4, trace=False)
    _well_formed(line, cell, traced=False)
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "query_p50_ms",
                                    "query_p95_ms", "doc_visible_p50_ms",
                                    "peak_hbm_gib"}
    # a live document waits at least for the next tick, and not for ever
    assert 10 < line["metrics"]["doc_visible_p50_ms"]["value"] < 3000
    # 20 queries/s and 4 documents/s for 4 s
    assert 70 <= line["attempted"] <= 125
    assert "breakdown" not in line

    from pathway_tpu.internals.parse_graph import G

    G.clear()
    traced = _run(cell, tmp_path, seconds=4, trace=True)
    _well_formed(traced, cell, traced=True)
    assert traced["correct"]
    got = traced["metrics"]
    # the six stages telescope: what the tracker saw of a request is there
    for stage in ("queue", "host", "device", "response_write"):
        assert got[f"request.{stage}_ms_p50"]["value"] >= 0
    assert got["scan.device_ms_p50"]["value"] > 0
    assert 0 < got["query.device_idle_share"]["value"] < 100
    assert got["query.batch_mean"]["value"] >= 1
    assert 0 < traced["device"]["busy_s"] < traced["device"]["window_s"]
    assert 0 < len(traced["breakdown"]["device_ops"]) <= 10
    assert 0 < len(traced["breakdown"]["idle_gaps"]) <= 10
    # the detail of both runs is in the output directory, the profile is not
    names = os.listdir(tmp_path)
    assert f"{cell.name}.seed3.trace0.json" in names
    assert f"{cell.name}.seed3.trace1.json" in names
    assert not [n for n in names if n.endswith(".xplane.pb")]


def test_ingest_backlog_traced(tmp_path):
    cell = tiny_cell("bge-small-10m.ingest-backlog")
    line = _run(cell, tmp_path, seconds=3, trace=True)
    _well_formed(line, cell, traced=True)
    got = line["metrics"]
    assert got["ingest.rows_per_tick"]["value"] > 1
    assert got["ingest.fused_fallbacks"]["value"] == 0
    assert got["tokenizer.alone_docs_per_s"]["value"] > 0
    assert got["ingest.pack_share"]["value"] \
        <= got["ingest.add_batch_share"]["value"] <= 100
    # read only where a whole call of the packer lies inside the traced
    # part of the window, which a CPU's seconds-long ticks may not grant
    assert got.get("encoder_roofline", {"value": 1.0})["value"] > 0
    with open(tmp_path / f"{cell.name}.seed3.trace1.json") as f:
        detail = json.load(f)
    assert detail["end_to_end"]["ingest_docs_per_s"] > 0
    # the filler rows are live and are not counted as documents
    edges = detail["edges"]
    assert edges["before"]["capacity_rows"] == 65536
    assert 0 < edges["before"]["rows"] < edges["after"]["rows"] <= 40000
    assert detail["checks"]["min_cos"] >= 0.999
    # 32 self-retrievals over the length range came back first
    assert not [f for f in detail["failures"] if "self-retrieval" in f]


def test_another_architecture_is_new_files_and_entries(tmp_path):
    cell = toy_cell(tmp_path / "checkout")
    assert cell.model.__file__.startswith(str(tmp_path))
    line = _run(cell, tmp_path, seconds=4, trace=False)
    _well_formed(line, cell, traced=False)
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "query_p50_ms",
                                    "query_p95_ms", "doc_visible_p50_ms",
                                    "peak_hbm_gib"}
    with open(tmp_path / f"{cell.name}.seed3.trace0.json") as f:
        detail = json.load(f)
    assert detail["checks"]["min_cos"] >= cell.reference.MIN_COS
    assert detail["checks"]["reference_rank"]["checked"] > 20

    from pathway_tpu.internals.parse_graph import G

    G.clear()
    traced = _run(cell, tmp_path, seconds=4, trace=True)
    _well_formed(traced, cell, traced=True)
    assert traced["correct"]
    got = traced["metrics"]
    # the toy's jitted forward is named as the program's own is, so the
    # query path's encoder readers find it; the scan is the index's
    assert got["encoder.device_ms_p50"]["value"] > 0
    assert got["scan.device_ms_p50"]["value"] > 0
    assert got["scan_roofline"]["value"] > 0
    assert 0 < len(traced["breakdown"]["device_ops"]) <= 10


def test_an_architecture_s_wrong_reference_fails_correct(tmp_path):
    with open(os.path.join(TOY, "reference.py")) as f:
        source = f.read()
    # pools the padding too: a structural fault, far under MIN_COS
    wrong = source.replace("table[ids[:n]].mean(axis=0)",
                           "table[ids].mean(axis=0)")
    assert wrong != source
    cell = toy_cell(tmp_path / "checkout", reference=wrong)
    line = _run(cell, tmp_path, seconds=3, trace=False)
    assert line["correct"] is False
    min_cos = line["compared"]["min_cos"]
    assert min_cos["value"] < 0.99 < min_cos["limit"] == 0.999995
    mean_cos = line["compared"]["mean_cos"]
    assert mean_cos["value"] < mean_cos["limit"] == 0.999998
    with open(tmp_path / f"{cell.name}.seed3.trace0.json") as f:
        failures = json.load(f)["failures"]
    assert any("encoder disagrees with the reference" in f for f in failures)


def test_the_function_refuses_the_wrong_platform_before_building(tmp_path):
    cell = tiny_cell("bge-small-10m.query-steady")
    with pytest.raises(runner.Refused, match="'cpu'.*needs 'tpu'"):
        runner.run_cell(cell, seed=0, seconds=1, trace=False,
                        expected_platform="tpu",
                        t_start=time.perf_counter(), out_dir=str(tmp_path))
    assert not os.listdir(tmp_path)
    import dataclasses

    four = dataclasses.replace(cell, chips=64)
    with pytest.raises(runner.Refused, match="asks for 64 chips"):
        _run(four, tmp_path, seconds=1, trace=False)


def test_an_unknown_device_kind_has_no_peaks(tmp_path):
    cell = tiny_cell("bge-small-10m.query-steady")
    with pytest.raises(KeyError, match="no peaks for device kind 'cpu'"):
        runner.run_cell(cell, seed=0, seconds=1, trace=False,
                        expected_platform="cpu",
                        t_start=time.perf_counter(), out_dir=str(tmp_path))


@pytest.mark.parametrize("workload", ["bge-small-10m.query-steady",
                                      "no-such-cell"])
def test_the_command_refuses_a_cpu_whatever_the_environment(workload):
    """No flag and no variable makes the command run off the chip: non-zero
    exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", EXPECTED_PLATFORM="cpu",
               BENCHMARK_PLATFORM="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", workload, "--seed", "0", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=180, env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "FAILED" in proc.stderr
