"""The start-up check: every name in BENCHMARK.json resolves to a file, so
a later PR's added entry fails loudly and early — and a cell, a mix and a
per-layer metric can each be added by new files plus one entry."""

from __future__ import annotations

import json
import os
import shutil

import pytest

from benchmark.lib import spec

from conftest import ROOT


@pytest.fixture
def checkout(tmp_path):
    """BENCHMARK.json and the benchmark's data and readers, copied where a
    test may add to them."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for part in ("configs", "traffic", "layers", "end_to_end", "models",
                 "reference"):
        shutil.copytree(os.path.join(ROOT, "benchmark", part),
                        tmp_path / "benchmark" / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def _edit(checkout, change) -> None:
    path = checkout / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    change(bench)
    path.write_text(json.dumps(bench))


def test_the_repo_s_own_benchmark_resolves():
    loaded = spec.load(ROOT)
    assert set(loaded.cells) == {"bge-small-10m.ingest-backlog",
                                 "bge-small-10m.query-steady"}
    for cell in loaded.cells.values():
        assert "setup_s" in {m.name for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.layers
        assert cell.chips == cell.config["chips"] == 1
        # the configuration's "model" found both of the architecture's files
        assert cell.config["model"] == "bert"
        assert cell.model.__file__.endswith("benchmark/models/bert.py")
        assert cell.reference.__file__.endswith(
            "benchmark/reference/bert.py")
    with pytest.raises(spec.SpecError, match="no workload 'nope'"):
        loaded.cell("nope")


def test_a_mix_a_cell_and_a_metric_are_added_by_files_and_entries(checkout):
    """The README's worked example: a read-only twin of query-steady (no
    live documents), a cell that runs it and a per-layer metric reported
    there, by new files and entries only."""
    mix = json.loads((checkout / "benchmark/traffic/query-steady.json")
                     .read_text())
    mix["name"] = "query-readonly"
    del mix["documents"]
    (checkout / "benchmark/traffic/query-readonly.json").write_text(
        json.dumps(mix))
    (checkout / "benchmark/layers/readonly.late_ms_max.py").write_text(
        '"""How late the generator ran at worst."""\n\n\n'
        "def read(run):\n"
        "    late = [(r.sent - r.due) * 1e3 for r in run.window_queries()]\n"
        "    return max(late) if late else None\n")

    def change(bench):
        bench["workloads"].append({
            "name": "bge-small-10m.query-readonly", "config": "bge-small-10m",
            "traffic": "query-readonly", "chips": 1, "why": "no writes"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            # every metric of query-steady but those of its live documents
            if "bge-small-10m.query-steady" in m.get("workloads", ()) \
                    and "visible" not in m["name"] \
                    and m.get("moves") != "doc_visible_p50_ms":
                m["workloads"].append("bge-small-10m.query-readonly")
        bench["per_layer"].append({
            "name": "readonly.late_ms_max", "unit": "ms", "better": "lower",
            "source": "host_clock", "layer": "benchmark",
            "moves": "query_p95_ms",
            "workloads": ["bge-small-10m.query-readonly"]})

    _edit(checkout, change)
    loaded = spec.load(str(checkout))
    readonly = loaded.cell("bge-small-10m.query-readonly")
    steady = loaded.cell("bge-small-10m.query-steady")
    assert "documents" not in readonly.traffic
    assert {m.name for m in steady.end_to_end} \
        - {m.name for m in readonly.end_to_end} == {"doc_visible_p50_ms"}
    assert {m.name for m in readonly.layers} \
        - {m.name for m in steady.layers} == {"readonly.late_ms_max"}


@pytest.mark.parametrize("change, message", [
    (lambda b: b["workloads"][0].update(traffic="no-such-mix"),
     r"benchmark/traffic/no-such-mix.json does not exist"),
    (lambda b: b["workloads"][0].update(config="no-such-config"),
     r"names the configuration 'no-such-config'"),
    (lambda b: b["per_layer"].append({
        "name": "no.reader", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "x", "moves": "setup_s"}),
     r"'no.reader' has no reader benchmark/layers/no.reader.py"),
    (lambda b: b["per_layer"][0].update(moves="no_such_metric"),
     r"moves 'no_such_metric', which is no end-to-end metric"),
    (lambda b: b["per_layer"][0].update(workloads=["no.such.cell"]),
     r"lists the workload 'no.such.cell'"),
    (lambda b: b["per_layer"][0].update(source="a_guess"),
     r"unknown source 'a_guess'"),
    (lambda b: b["end_to_end"][1].update(source="program_counter"),
     r"must come from host_clock or device_trace"),
    (lambda b: b["workloads"][0].update(chips=4),
     r"asks for 4 chips but its configuration is laid out for 1"),
    # a per-layer metric reported where the metric it moves is not
    (lambda b: [m for m in b["per_layer"]
                if m["name"] == "scan_roofline"][0].pop("workloads"),
     r"'scan_roofline' is reported in 'bge-small-10m.ingest-backlog' but "
     r"the metric it moves, 'query_p50_ms', is not"),
    (lambda b: b["configs"].append({
        "name": "unused", "source": "x", "reduced": [], "why": "",
        "file": "benchmark/configs/bge-small-10m.json"}),
     r"configurations used by no workload: \['unused'\]"),
])
def test_what_does_not_resolve_is_named_before_anything_is_built(
        checkout, change, message):
    _edit(checkout, change)
    with pytest.raises(spec.SpecError, match=message):
        spec.load(str(checkout))


def _config(checkout, change) -> None:
    path = checkout / "benchmark/configs/bge-small-10m.json"
    config = json.loads(path.read_text())
    change(config)
    path.write_text(json.dumps(config))


def _strip(checkout, rel: str, name: str) -> None:
    """Take the definition of ``name`` out of the file ``rel``."""
    path = checkout / rel
    path.write_text(path.read_text().replace(f"def {name}(", f"def _{name}(")
                    .replace(f"\n{name} = ", f"\n_{name} = "))


@pytest.mark.parametrize("change, message", [
    (lambda c: _config(c, lambda cfg: cfg.pop("model")),
     r"configuration 'bge-small-10m' names no \"model\""),
    (lambda c: _config(c, lambda cfg: cfg.update(model="mamba")),
     r"names the model 'mamba', which has no benchmark/models/mamba.py"),
    (lambda c: os.remove(c / "benchmark/reference/bert.py"),
     r"names the model 'bert', which has no reference "
     r"benchmark/reference/bert.py"),
    (lambda c: _strip(c, "benchmark/models/bert.py", "build"),
     r"benchmark/models/bert.py defines no build\(\)"),
    (lambda c: _strip(c, "benchmark/models/bert.py", "tokens"),
     r"benchmark/models/bert.py defines no tokens\(\)"),
    (lambda c: _strip(c, "benchmark/models/bert.py", "dispatch_cost"),
     r"benchmark/models/bert.py defines no dispatch_cost\(\)"),
    (lambda c: _strip(c, "benchmark/reference/bert.py", "embed"),
     r"benchmark/reference/bert.py defines no embed\(\)"),
    (lambda c: _strip(c, "benchmark/reference/bert.py", "weights"),
     r"benchmark/reference/bert.py defines no weights\(\)"),
    (lambda c: _strip(c, "benchmark/reference/bert.py", "control"),
     r"benchmark/reference/bert.py defines no control\(\)"),
    (lambda c: _strip(c, "benchmark/reference/bert.py", "MIN_COS"),
     r"benchmark/reference/bert.py defines no MIN_COS in \(0, 1\]"),
    (lambda c: _strip(c, "benchmark/reference/bert.py", "MIN_MEAN_COS"),
     r"benchmark/reference/bert.py defines no MIN_MEAN_COS in \(0, 1\]"),
])
def test_a_model_s_missing_file_or_callable_is_named(checkout, change,
                                                     message):
    change(checkout)
    with pytest.raises(spec.SpecError, match=message):
        spec.load(str(checkout))
