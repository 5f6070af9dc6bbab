"""Percent of the visible (query, key) pairs that the attention layers
attended over, summed over every dispatch of the run and every attention
layer: the device's own counts (``embedder.expert_load()``'s
``selected_pairs``, counted from the choices themselves, over its
``visible_pairs``; two device scalars fetched here, after the window). A
query attends over all it sees or ``index_topk``, the fewer, so over the
same dispatches it equals the ``embedder.dispatch`` spans'
``attn_pairs_selected`` over their ``attn_pairs_full`` times the attention
layers, or the program is wrong. None where the program's model chooses no
keys, or counts none."""


def read(run):
    load = getattr(run.extras["system"].embedder, "expert_load", None)
    found = load() if callable(load) else None
    if not found or not found.get("visible_pairs"):
        return None
    return 100.0 * found["selected_pairs"] / found["visible_pairs"]
