"""Percent of the chosen (token, expert) pairs of real tokens that took an
identity expert (no product, no row of the pair buffer), summed over every
dispatch of the run and every layer: the embedder's own count
(``embedder.expert_load()``'s ``zero_pairs`` over ``pairs``, two device
scalars fetched here, after the window). None where the program's router
has no identity experts, or counts none."""


def read(run):
    load = getattr(run.extras["system"].embedder, "expert_load", None)
    found = load() if callable(load) else None
    if not found or not found.get("pairs"):
        return None
    return 100.0 * found["zero_pairs"] / found["pairs"]
