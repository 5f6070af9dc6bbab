"""The busiest held expert's tokens over the mean held expert's, summed
over every dispatch of the run and every layer: 1 under even routing, which
the cost functions assume. From the embedder's own count
(``embedder.expert_load()``, one device array fetched here, after the
window)."""


def read(run):
    load = getattr(run.extras["system"].embedder, "expert_load", None)
    found = load() if callable(load) else None
    if not found:
        return None
    tokens = found["tokens_per_expert"]
    mean = float(tokens.mean())
    return float(tokens.max()) / mean if mean else None
