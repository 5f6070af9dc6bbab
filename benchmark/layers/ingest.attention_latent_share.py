"""Percent of the fused ingest program's device time in the traced window
spent in latent attention's own part of a sublayer (the scope
``decoder.attention.latent``, inside ``decoder.attention``): the four
low-rank projections, their norms and scales, the rotary turn; beside
``ingest.attention_full_share``, the cores, and ``ingest.attention_share``,
which holds both and the output projection. None where the program has no
such scope."""

from benchmark.lib.scope_readers import share


def read(run):
    return share(run, ("decoder.attention.latent",))
