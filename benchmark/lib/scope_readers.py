"""Arithmetic shared by the readers of a kernel that lives inside the fused
ingest program and is found there by its named scope
(``lib/readers.py`` ``scope_seconds``). Every function returns None where
the run holds nothing to read: no trace, no such scope (a program from
before it had one; the CPU backend, which keeps no scopes), no packer span,
an architecture without the cost function."""

from __future__ import annotations

import re

from benchmark.lib import program_spans, trace as tracelib
from benchmark.lib.readers import module_runs, scope_seconds

#: the TPU compiler's name for the grouped matmul it makes of
#: ``jax.lax.ragged_dot``. It gives those operations its own name in place
#: of JAX's name stack (``op_name="ragged-dot-none"``), so no scope finds
#: them: they are the routed experts' products whatever scope called them
GROUPED_MATMUL = "ragged-dot"


def primitive_seconds(run, kernel: str, prefix: str) -> float:
    """Device self seconds in the traced window of the operations of
    ``kernel``'s programs whose primitive (the part of the operation's path
    before its own name) starts with ``prefix``, on the chip that spent
    most there; 0.0 where there is none."""
    if run.trace is None:
        return 0.0
    pat = re.compile(tracelib.MODULE_PATTERNS[kernel])

    def matches(name: str) -> bool:
        module, *path = name.split("/")
        return bool(pat.match(module)) and any(
            part.startswith(prefix) for part in path[:-1])

    return max((sum(s for name, s in d.ops.items() if matches(name))
                for d in run.trace.devices), default=0.0)


def fill_share(run) -> float:
    """Real tokens over slots (packed rows x serving width) of the traced
    window's ``embedder.dispatch`` spans; 1.0 where the program has no such
    span."""
    spans = program_spans.named(run, "embedder.dispatch", *run.traced) \
        if run.traced else None
    width = run.cell.config["serving"]["max_len"]
    slots = sum((sp[5] or {}).get("rows", 0) for sp in spans or ()) * width
    if not slots:
        return 1.0
    return sum((sp[5] or {}).get("tokens", 0) for sp in spans) / slots


def roofline(run, scope: str, cost_name: str,
             primitive: str | None = None) -> float | None:
    """Percent: the least time the chip could take for the **real tokens**
    of the shapes the packer dispatched in the traced window (padding is
    no useful work: :func:`fill_share`), by the cell's architecture's
    ``<cost_name>(config, shape, fill) -> (flops, bytes)``, over the device
    time under ``scope`` of the fused ingest program (and, where the
    kernel's operations carry the compiler's name and no scope, of the
    operations of ``primitive``)."""
    measured = scope_seconds(run, "fused_ingest", scope)
    if measured and primitive:
        measured += primitive_seconds(run, "fused_ingest", primitive)
    packs = run.spans_in("pack", run.traced)
    cost = getattr(run.cell.model, cost_name, None)
    if not measured or not packs or cost is None:
        return None
    peaks = run.extras["peaks"]
    fill = fill_share(run)
    least = 0.0
    for _s, _e, meta in packs:
        for shape in meta["shapes"]:
            flops, nbytes = cost(run.cell.config, tuple(shape), fill)
            least += max(flops / peaks["flops_per_s"],
                         nbytes / peaks["bytes_per_s"])
    return 100.0 * least / measured


def share(run, scopes: tuple[str, ...],
          primitive: str | None = None) -> float | None:
    """Percent of the fused ingest program's device time in the traced
    window spent under ``scopes`` (and in the operations of ``primitive``,
    as in :func:`roofline`)."""
    runs = module_runs(run, ("fused_ingest",))
    inside = [scope_seconds(run, "fused_ingest", scope) for scope in scopes]
    if runs is None or not any(inside):
        return None
    total = sum(s or 0.0 for s in inside)
    if primitive:
        total += primitive_seconds(run, "fused_ingest", primitive)
    return 100.0 * total / max(sum(chip) for chip in runs)
