"""Percent of the window the costliest operator spent in its steps, host
and device leg alike, from the recorder's operator events; the five
costliest are printed by name for the reader of the run's output."""

from benchmark.lib.program_spans import operator_shares


def read(run):
    shares = operator_shares(run)
    if not shares:
        return None
    print("ingest.top_operator_share: " + "; ".join(
        f"{name} {share:.2f}%" for name, share in shares[:5]), flush=True)
    return shares[0][1]
