"""The builder's reading of the numbers that ``MIN_COS`` and ``MIN_MEAN_COS``
stand between, and of what the control does to the exact top-k's deficit.

    python3 benchmark/tools/control.py <cell> <seed> [<seed> ...]

For each seed, in one process on the chip (it refuses any other platform),
at the cell's own sizes: the architecture's embedder with weights from the
seed, the 64 documents of the mix that a run's check samples (spread over
the length range), and

- ``program``: 1 - the least and 1 - the mean cosine between the program's
  served embeddings and the plain reference's: what a sound run reads (the
  lower readings);
- ``control``: the same with the reference's ``control`` (the forward pass
  in the nearest precision below the configuration's) in the program's
  place (the upper reading): ``correct`` has to refuse it; and under
  ``control.<kind>`` the two cosines of every other lower precision the
  reference names (``CONTROL_KINDS``), refused or not;
- ``rank_deficit``: over the mix's documents and as many queries as a window
  sends, how far the first hit by each side's embeddings scores under the
  best in the reference's float32 cosine (``check.RANK_TOLERANCE``'s number),
  and how far the second hit does: the fault of two hits changing places.

One JSON line a seed; the driver never runs this. ``readings`` is what
``benchmark/tests/test_control.py`` calls at the published shape on the CPU.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import tempfile

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def documents(cell, seed: int) -> list[str]:
    """The mix's documents a run indexes before its window."""
    from benchmark.lib import traffic

    section = "corpus" if "corpus" in cell.traffic else "backlog"
    return traffic.corpus_texts(cell.traffic, section, seed)[:2000]


def readings(cell, seed: int, docs: list[str], n_sample: int,
             n_queries: int) -> dict:
    from benchmark.lib import check, runner, traffic
    from benchmark.lib.vector_store import System

    with tempfile.TemporaryDirectory(prefix="control_") as workdir:
        system = System(cell, seed, workdir, log=lambda _m: None)
        system.make_embedder()
        sample = [docs[i] for i in runner._spread_by_length(docs, n_sample)]
        out = {"seed": seed}
        program = system.served_embeddings
        ref = system.reference
        kinds = list(getattr(ref, "CONTROL_KINDS", ()))

        def control(**kind):
            return lambda texts: check.reference_embeddings(
                system, texts, embed=functools.partial(ref.control, **kind))

        queries = [traffic.cut_span(np.random.default_rng([seed, i]),
                                    docs[i % len(docs)], 8)
                   for i in range(n_queries)]
        if queries:
            emb = check.reference_embeddings(system, docs + queries)
            scores = check.cosine_scores(emb[len(docs):], emb[:len(docs)])
        sides = [("program", program), ("control", control())] \
            + [(f"control.{kind}", control(kind=kind)) for kind in kinds[1:]]
        for side, embed in sides:
            system.served_embeddings = embed
            fails, cos = check.embeddings_agree(system, sample)
            out[side] = {"one_minus_min_cos": 1.0 - cos["min_cos"],
                         "one_minus_mean_cos": 1.0 - cos["mean_cos"],
                         "refused": bool(fails)}
            if queries and "." not in side:
                got = embed(docs + queries)
                order = np.argsort(-check.cosine_scores(
                    got[len(docs):], got[:len(docs)]), axis=1)
                for name, place in (("rank_deficit_max", 0),
                                    ("rank_deficit_max_hits_swapped", 1)):
                    deficit = scores.max(axis=1) - scores[
                        np.arange(len(queries)), order[:, place]]
                    out[side][name] = float(deficit.max())
        return out


def main(argv: list[str]) -> int:
    if len(argv) < 2:
        print(__doc__, file=sys.stderr)
        return 2
    import jax

    import pathway_tpu as pw
    from benchmark.lib import spec

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"control: JAX runs on {device.platform!r}; the readings a "
              f"limit is set from are the chip's", file=sys.stderr)
        return 1
    pw.enable_compilation_cache()
    cell = spec.load(ROOT).cell(argv[0])
    n_sample = cell.traffic.get("after", {}).get("embedding_sample", 64)
    for seed in map(int, argv[1:]):
        row = readings(cell, seed, documents(cell, seed), n_sample, 600)
        print(json.dumps(dict(row, cell=cell.name, kind=device.device_kind)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
