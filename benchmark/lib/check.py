"""The comparisons that decide ``correct``. Each returns the list of what
failed (empty where all held) and prints nothing: the runner reports."""

from __future__ import annotations

import numpy as np

from benchmark.reference import bert

# bf16 vs float32 encoder agreement, as the cosine between the two unit
# embeddings of one text. bfloat16 keeps 8 significant bits; over 12 post-LN
# layers with float32 accumulation and float32 layernorm the roundings add
# like a random walk, and the bf16 path also swaps erf-GELU for tanh-GELU
# (<= 3e-3 abs). At the BGE-small shape on seeded weights that leaves
# 1 - cos about 5e-5 (PERF.md, PR 21 and PR 22). 0.999 is 20 times that and
# still fails on any structural fault: a wrong mask, a dropped layer, a
# document attending its neighbour in a packed sequence all land below it,
# since two different documents are only 0.99 alike.
MIN_COS = 0.999

# how far below the reference's best score the served first hit may score
# in the reference's own float32 arithmetic. Seeded random weights put all
# documents within 0.03 of each other in cosine, so near ties are the rule
# and equality of names cannot be asked; the served path's bf16 rounding
# moves a score by about 1e-4 (measured deficits up to 1.2e-4), while the
# median wrong document scores 5e-3 lower. 1e-3 lies ten times above the
# one and five times below the other.
RANK_TOLERANCE = 1e-3


def reference_embeddings(system, texts: list[str]) -> np.ndarray:
    cfg = system.encoder_config
    ids, lengths = system.tokens(texts)
    return bert.embed(system.embedder.params, ids, lengths, heads=cfg.heads,
                      eps=cfg.layer_norm_eps)


def embeddings_agree(system, texts: list[str]) -> tuple[list[str], float]:
    """The program's encoder path against the plain reference on ``texts``:
    (failures, the smallest cosine)."""
    ref = reference_embeddings(system, texts)
    got = system.served_embeddings(texts)
    if got.shape != ref.shape or not np.isfinite(got).all():
        return [f"served embeddings have shape {got.shape}, finite="
                f"{bool(np.isfinite(got).all())}; reference {ref.shape}"], 0.0
    cos = np.sum(got * ref, axis=1) / (
        np.linalg.norm(got, axis=1) * np.linalg.norm(ref, axis=1))
    worst = float(cos.min())
    fails = [] if worst >= MIN_COS else [
        f"encoder disagrees with the reference: min cos {worst:.6f} < "
        f"{MIN_COS} (text {int(cos.argmin())} of {len(texts)})"]
    return fails, worst


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
    scores = bert.cosine_scores(emb[len(names):], emb[:len(names)])
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
