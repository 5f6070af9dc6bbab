"""``BENCHMARK.json`` and the files it names, loaded and checked.

A cell is ``{name, config, traffic, chips}`` in ``workloads`` and nothing
else. Its configuration is the ``file`` of its ``configs`` entry, its mix
``benchmark/traffic/<traffic>.json``, and every metric a reader of its own:
``benchmark/end_to_end/<metric>.py`` or ``benchmark/layers/<metric>.py``,
each defining ``read(run)``. A configuration names its architecture
(``"model"``), and that name finds the two files that know it:
``benchmark/models/<model>.py`` (how the program's embedder is built, what
it is fed and what a dispatch costs) and ``benchmark/reference/<model>.py``
(the weights from the seed, the plain forward pass over them, its control
in a lower precision and the tolerance between the two). :func:`load`
resolves all of them before anything is built, so a later PR's added file
fails loudly and early.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import numbers
import os
from types import ModuleType
from typing import Callable

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
#: what a file found by name has to define (benchmark/README.md gives the
#: signatures)
MODEL_CALLABLES = ("build", "tokens", "dispatch_cost")
REFERENCE_CALLABLES = ("weights", "embed", "control")


class SpecError(ValueError):
    """``BENCHMARK.json`` and the benchmark's files do not fit together."""


@dataclasses.dataclass(frozen=True)
class Metric:
    """One metric of ``BENCHMARK.json`` with the reader that takes it from
    what a run collected (:class:`benchmark.lib.record.Run`). ``read(run)``
    returns a number, or None where there was nothing to read."""

    name: str
    unit: str
    read: Callable


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple[Metric, ...]   # the metrics reported in this cell
    layers: tuple[Metric, ...]
    model: ModuleType       # benchmark/models/<config["model"]>.py
    reference: ModuleType   # benchmark/reference/<config["model"]>.py


@dataclasses.dataclass(frozen=True)
class Spec:
    benchmark: dict
    cells: dict[str, Cell]

    def cell(self, name: str) -> Cell:
        try:
            return self.cells[name]
        except KeyError:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json; it has "
                            f"{sorted(self.cells)}") from None


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"{os.path.relpath(path, ROOT)} does not exist") \
            from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{os.path.relpath(path, ROOT)}: {e}") from None


def _load_file(root: str, kind: str, name: str, callables: tuple[str, ...],
               missing: str) -> ModuleType:
    """The module of ``benchmark/<kind>/<name>.py`` under ``root``, which
    has to define every one of ``callables``."""
    rel = f"benchmark/{kind}/{name}.py"
    path = os.path.join(root, "benchmark", kind, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"{missing} {rel}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}." + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    for fn in callables:
        if not callable(getattr(module, fn, None)):
            raise SpecError(f"{rel} defines no {fn}()")
    return module


def _load_reader(root: str, kind: str, name: str):
    return _load_file(root, kind, name, ("read",),
                      f"metric {name!r} has no reader").read


def _load_model(root: str, config_name: str, config: dict
                ) -> tuple[ModuleType, ModuleType]:
    """The two files of the architecture a configuration names."""
    name = config.get("model")
    if not isinstance(name, str) or not name:
        raise SpecError(f"configuration {config_name!r} names no \"model\": "
                        f"the key finds benchmark/models/<model>.py and "
                        f"benchmark/reference/<model>.py")
    what = f"configuration {config_name!r} names the model {name!r}, which"
    model = _load_file(root, "models", name, MODEL_CALLABLES,
                       f"{what} has no")
    reference = _load_file(root, "reference", name, REFERENCE_CALLABLES,
                           f"{what} has no reference")
    for limit in ("MIN_COS", "MIN_MEAN_COS"):
        value = getattr(reference, limit, None)
        if not isinstance(value, numbers.Real) or not 0.0 < value <= 1.0:
            raise SpecError(
                f"benchmark/reference/{name}.py defines no {limit} in (0, 1]:"
                f" the least cosine between a served embedding and the "
                f"reference's, of one text and in the mean")
    return model, reference


def _applies(metric: dict, cell_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell_name in cells


def load(root: str = ROOT) -> Spec:
    """Load and check everything. Raises :class:`SpecError` naming the first
    thing that does not resolve."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    configs = {c["name"]: c for c in bench["configs"]}
    workloads = {w["name"]: w for w in bench["workloads"]}
    e2e_names = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["source"] not in SOURCES:
            raise SpecError(f"metric {m['name']!r}: unknown source "
                            f"{m['source']!r}")
        for w in m.get("workloads", ()):
            if w not in workloads:
                raise SpecError(f"metric {m['name']!r} lists the workload "
                                f"{w!r}, which BENCHMARK.json does not have")
    for m in bench["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            raise SpecError(f"end-to-end metric {m['name']!r} must come from "
                            f"host_clock or device_trace")
    readers = {m["name"]: _load_reader(root, "end_to_end", m["name"])
               for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        if m["moves"] not in e2e_names:
            raise SpecError(f"per-layer metric {m['name']!r} moves "
                            f"{m['moves']!r}, which is no end-to-end metric")
        readers[m["name"]] = _load_reader(root, "layers", m["name"])
    used = set()
    cells: dict[str, Cell] = {}
    models: dict[str, tuple[ModuleType, ModuleType]] = {}
    for name, w in workloads.items():
        if w["config"] not in configs:
            raise SpecError(f"workload {name!r} names the configuration "
                            f"{w['config']!r}, which BENCHMARK.json lacks")
        used.add(w["config"])
        config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
        traffic = _read_json(os.path.join(
            root, "benchmark", "traffic", w["traffic"] + ".json"))
        if w["config"] not in models:
            models[w["config"]] = _load_model(root, w["config"], config)
        if config.get("chips") != w["chips"]:
            raise SpecError(
                f"workload {name!r} asks for {w['chips']} chips but its "
                f"configuration is laid out for {config.get('chips')}")
        e2e = tuple(Metric(m["name"], m["unit"], readers[m["name"]])
                    for m in bench["end_to_end"] if _applies(m, name))
        here = {m.name for m in e2e}
        if "setup_s" not in here or len(here) < 2:
            raise SpecError(f"workload {name!r} must report setup_s and at "
                            f"least one other end-to-end metric")
        layers = []
        for m in bench["per_layer"]:
            if not _applies(m, name):
                continue
            if m["moves"] not in here:
                raise SpecError(
                    f"per-layer metric {m['name']!r} is reported in {name!r} "
                    f"but the metric it moves, {m['moves']!r}, is not")
            layers.append(Metric(m["name"], m["unit"], readers[m["name"]]))
        if not layers:
            raise SpecError(f"workload {name!r} reports no per-layer metric")
        cells[name] = Cell(name, config, traffic, int(w["chips"]), e2e,
                           tuple(layers), *models[w["config"]])
    unused = set(configs) - used
    if unused:
        raise SpecError(f"configurations used by no workload: "
                        f"{sorted(unused)}")
    return Spec(bench, cells)
