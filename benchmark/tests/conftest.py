"""The benchmark's own tests: ``python -m pytest benchmark/tests`` from the
root of the repo, on the CPU (nothing here states a speed). They are not
part of ``tests/``: a later PR may not change the yardstick, so its tests
live with it."""

from __future__ import annotations

import copy
import dataclasses
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: the rehearsal's second architecture, which no file of the harness knows
TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "toybag")
#: stands in for the peaks table's row in a rehearsal off the chip
FAKE_PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11, "source": "test"}


@pytest.fixture(autouse=True)
def _fresh_graph():
    """The program's parse graph is process-wide: one server per test."""
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    yield
    G.clear()


@pytest.fixture(autouse=True, scope="session")
def _no_cache_io():
    """Keep the CPU rehearsals out of the checkout's compile cache, which
    the chip's programs own."""
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    yield


def load_tool(name: str):
    """The module of ``benchmark/tools/<name>.py``: the builder's tools are
    scripts, not a package."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", "tools", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def tiny_mix(mix: dict) -> dict:
    """A traffic mix cut to what the CPU rehearses in seconds: short
    documents, few of them, short settling and tracing."""
    mix = copy.deepcopy(mix)
    for part in ("corpus", "backlog", "documents"):
        if part in mix:
            mix[part]["words"].update(mean=12, max=40)
    if "corpus" in mix:
        mix["corpus"]["docs"] = 60
    if "backlog" in mix:
        mix["backlog"].update(docs=40000, files_per_dir=4096)
        mix["warm"].update(ticks=4, quiet_ticks=2)
    if "settle_s" in mix:
        mix["settle_s"] = 2
    mix["trace_s"] = 2
    return mix


def tiny_cell(name: str):
    """The cell ``name`` cut to a size the CPU rehearses in seconds: a toy
    encoder, 20,000 filler rows, short documents, few of them."""
    from benchmark.lib import spec

    cell = spec.load(ROOT).cell(name)
    config = copy.deepcopy(cell.config)
    config.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                  intermediate_size=128, vocab_size=8192,
                  max_position_embeddings=128)
    config["serving"]["max_len"] = 48
    config["index"].update(rows=20000, reserved_rows=65536)
    return dataclasses.replace(cell, config=config,
                               traffic=tiny_mix(cell.traffic))


def toy_cell(root, reference: str | None = None):
    """A checkout under ``root`` to which a second architecture is added as
    a ``model_config`` PR adds one (benchmark/README.md, "Adding a
    configuration of another architecture"): three new files and new
    entries in BENCHMARK.json. There is no ``benchmark/lib/`` under
    ``root``: the harness is the repo's, untouched. ``reference`` stands in
    for the toy's reference where a test wants one that is wrong."""
    for part in ("configs", "traffic", "layers", "end_to_end", "models",
                 "reference"):
        shutil.copytree(os.path.join(ROOT, "benchmark", part),
                        root / "benchmark" / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for src, dst in (("model.py", "models/toybag.py"),
                     ("reference.py", "reference/toybag.py"),
                     ("config.json", "configs/toybag-20k.json")):
        shutil.copy(os.path.join(TOY, src), root / "benchmark" / dst)
    if reference is not None:
        (root / "benchmark/reference/toybag.py").write_text(reference)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toybag-20k", "source": "benchmark/tests/data/toybag",
        "file": "benchmark/configs/toybag-20k.json", "reduced": [],
        "why": "a mean-pooled embedding bag"})
    bench["workloads"].append({
        "name": "toybag-20k.query-steady", "config": "toybag-20k",
        "traffic": "query-steady", "chips": 1, "why": "the rehearsal's"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "bge-small-10m.query-steady" in m.get("workloads", ()):
            m["workloads"].append("toybag-20k.query-steady")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert not (root / "benchmark" / "lib").exists()
    from benchmark.lib import spec

    cell = spec.load(str(root)).cell("toybag-20k.query-steady")
    return dataclasses.replace(cell, traffic=tiny_mix(cell.traffic))
