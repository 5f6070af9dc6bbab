"""Real tokens (padding left out) a fused dispatch of the window carried, in
the mean: the program's ``embedder.dispatch`` spans' ``tokens``. The
dispatched shape holds ``rows x width`` slots; what the packer could not
fill is computed and thrown away."""

from benchmark.lib import program_spans


def read(run):
    spans = program_spans.started_in_window(run, "embedder.dispatch")
    if not spans:
        return None
    return sum((sp[5] or {}).get("tokens", 0) for sp in spans) / len(spans)
