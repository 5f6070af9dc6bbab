"""The blocked attention's share of its roofline, percent: the least time
for the visible (query, key) pairs of the traced window's dispatches
(``attention_cost`` of the cell's architecture over the program's
``embedder.dispatch`` spans' ``tokens``, ``attn_pairs_full`` and
``attn_pairs_window``: 4 x 128 flops a visible pair a query head; q, k, v
and o once in bfloat16) over the device time of the fused ingest program
under the scopes ``decoder.attention.full`` and ``decoder.attention.window``.
Blocks the kernel skips are not counted, so the share stays under 100. None
where the program has no such span field or scope, or the architecture no
``attention_cost``."""

from benchmark.lib import costs, program_spans
from benchmark.lib.readers import scope_seconds

SCOPES = ("decoder.attention.full", "decoder.attention.window")


def read(run):
    cost = getattr(run.cell.model, "attention_cost", None)
    spans = program_spans.named(run, "embedder.dispatch", *run.traced) \
        if run.traced else None
    counts = [sp[5] for sp in spans or () if "attn_pairs_full" in (sp[5] or {})]
    measured = sum(scope_seconds(run, "fused_ingest", scope) or 0.0
                   for scope in SCOPES)
    if cost is None or not counts or not measured:
        return None
    total = lambda key: float(sum(c.get(key, 0) for c in counts))
    flops, nbytes = cost(run.cell.config, total("tokens"),
                         total("attn_pairs_full"), total("attn_pairs_window"))
    share, _bound = costs.roofline(flops, nbytes, measured,
                                   run.extras["peaks"])
    return 100.0 * share
