"""A roofline share whose work is a count of (query, key) pairs that the
program's ``embedder.dispatch`` spans carry: what several readers of a
kernel inside the fused ingest program compute alike. None where the run
holds nothing to read (no trace, no such scope, no such span field, an
architecture without the cost function)."""

from __future__ import annotations

from benchmark.lib import costs, program_spans
from benchmark.lib.readers import scope_seconds


def read(run, cost_name: str, field: str, scope: str) -> float | None:
    """Percent: the least time for the pairs the traced window's dispatches
    count under ``field`` and their real ``tokens``, by the cell's
    architecture's ``<cost_name>(config, tokens, pairs) -> (flops, bytes)``,
    over the device time of the fused ingest program under ``scope``."""
    cost = getattr(run.cell.model, cost_name, None)
    spans = program_spans.named(run, "embedder.dispatch", *run.traced) \
        if run.traced else None
    counts = [sp[5] for sp in spans or () if field in (sp[5] or {})]
    measured = scope_seconds(run, "fused_ingest", scope)
    if cost is None or not counts or not measured:
        return None
    total = lambda key: float(sum(c.get(key, 0) for c in counts))
    flops, nbytes = cost(run.cell.config, total("tokens"), total(field))
    share, _bound = costs.roofline(flops, nbytes, measured,
                                   run.extras["peaks"])
    return 100.0 * share
