"""The comparisons that decide ``correct``. Each returns the list of what
failed (empty where all held) and prints nothing: the runner reports. The
plain forward pass and the cosine it is held to are the architecture's
(``system.reference``: ``benchmark/reference/<model>.py``); the exact top-k
over its embeddings is here, whatever the model."""

from __future__ import annotations

import numpy as np

# how far below the reference's best score the served first hit may score
# in the reference's own float32 arithmetic. Seeded random weights put all
# documents within 0.03 of each other in cosine, so near ties are the rule
# and equality of names cannot be asked. Readings on the chip (PERF.md
# section 2): sound runs up to 3.1e-4 (the bf16 slab and embeddings move a
# score by about 1e-4); with the first two hits changed places 9.4e-3 at the
# least over 15 seeds. The int8 control reads 2.8e-4 to 7.1e-4 and is not
# told apart here: a ranking among near ties is as robust to int8 as to
# bf16, and the cosines of the architecture's reference are what refuse it.
RANK_TOLERANCE = 1e-3


def cosine_scores(queries: np.ndarray, documents: np.ndarray) -> np.ndarray:
    """(n_queries, n_documents) exact float32 cosine similarities by one
    full matmul on the host: the exact top-k's reference."""
    q = queries / np.linalg.norm(queries, axis=1, keepdims=True)
    d = documents / np.linalg.norm(documents, axis=1, keepdims=True)
    return q.astype(np.float32) @ d.astype(np.float32).T


def reference_embeddings(system, texts: list[str], embed=None) -> np.ndarray:
    """The reference's embeddings of ``texts`` over weights it makes anew
    from the run's seed: nothing the program holds reaches it but the token
    ids. ``embed`` stands in for the reference's own (its ``control``)."""
    ref = system.reference
    ids, lengths = system.tokens(texts)
    return (embed or ref.embed)(ref.weights(system.config, system.seed), ids,
                                lengths, system.config)


def embeddings_agree(system, texts: list[str]) -> tuple[list[str], dict]:
    """The program's encoder path against the plain reference on ``texts``:
    (failures, the smallest and the mean cosine). The smallest, a widest
    gap, swings from seed to seed and catches one text gone wrong; the mean
    is steady and is what tells a lower precision from the served one."""
    ref_mod = system.reference
    ref = reference_embeddings(system, texts)
    got = system.served_embeddings(texts)
    if got.shape != ref.shape or not np.isfinite(got).all():
        return [f"served embeddings have shape {got.shape}, finite="
                f"{bool(np.isfinite(got).all())}; reference {ref.shape}"], \
            {"min_cos": 0.0, "mean_cos": 0.0}
    cos = np.sum(got * ref, axis=1, dtype=np.float64) / (
        np.linalg.norm(got, axis=1) * np.linalg.norm(ref, axis=1))
    found = {"min_cos": float(cos.min()), "mean_cos": float(cos.mean())}
    fails = [f"encoder disagrees with the reference: {name.replace('_', ' ')} "
             f"{found[name]:.6f} < {limit} (worst text {int(cos.argmin())} "
             f"of {len(texts)})"
             for name, limit in (("min_cos", ref_mod.MIN_COS),
                                 ("mean_cos", ref_mod.MIN_MEAN_COS))
             if found[name] < limit]
    return fails, found


def first_hits_own(queries: list, what: str) -> list[str]:
    """Each query that names a document (a self-retrieval, a read-your-write
    check) must get that document back first."""
    return [f"{what}: {q.event.doc} asked {q.sent - q.due:+.3f}s after due "
            f"came back {q.hits[:1] or 'nothing'}"
            for q in queries if q.error is None
            and q.hits[:1] != (q.event.doc,)]


def first_hits_match_reference(system, queries: list, docs: dict[str, str],
                               written_at: dict[str, float],
                               visible_s: float) -> tuple[list[str], dict]:
    """Exact-search check of the queries that name no document. ``docs``
    maps every real document's file name to its text; ``written_at`` gives
    the instant each live document was written (the rest were indexed in
    set-up). A query must see every document written ``visible_s`` before it
    was sent, and may see a later one (which then scores higher still). Its
    served first hit must score, in the reference's float32 cosine, within
    :data:`RANK_TOLERANCE` of the best document it must see."""
    queries = [q for q in queries if q.error is None]
    if not queries:
        return [], {"checked": 0}
    names = list(docs)
    col = {name: i for i, name in enumerate(names)}
    emb = reference_embeddings(system, [docs[n] for n in names]
                               + [q.event.text for q in queries])
    scores = cosine_scores(emb[len(names):], emb[:len(names)])
    born = np.array([written_at.get(n, float("-inf")) for n in names])
    fails, deficits, unknown, same = [], [], 0, 0
    for row, q in zip(scores, queries):
        must = born <= q.sent - visible_s
        hit = q.hits[0] if q.hits else None
        if hit not in col:
            unknown += 1
            fails.append(f"query due {q.due:.3f} returned {hit!r}, which is "
                         f"no document of this run")
            continue
        visible = np.where(must, row, -np.inf)
        deficit = float(visible.max() - row[col[hit]])
        deficits.append(deficit)
        same += int(visible.argmax() == col[hit])
        if born[col[hit]] > q.done:
            fails.append(f"query due {q.due:.3f} returned {hit}, written "
                         f"after the answer came back")
        elif deficit > RANK_TOLERANCE:
            fails.append(
                f"query {q.event.text!r} returned {hit} scoring "
                f"{deficit:.2e} under the reference's best visible "
                f"document (tolerance {RANK_TOLERANCE})")
    return fails, {"checked": len(queries), "not_a_document": unknown,
                   "max_deficit": max(deficits, default=0.0),
                   "same_name_as_reference": same}
