"""Plain reference of the embedding bag (``"model": "toybag"``): numpy,
float32, a loop over the texts."""

from __future__ import annotations

import numpy as np

# 1 - cos, read on the CPU at the toy's own size on four seeds (PR 27): one
# bfloat16 rounding of each table row on the served side, none here, and a
# float32 mean on both: the worst of 64 texts 8.1e-7 at the most, their mean
# 2.7e-7; the int8 ``control`` 2.0e-5 and 7.8e-6 at the least. A bag that
# pools its padding too, or half of its words, lands under 0.99.
MIN_COS = 0.999995
MIN_MEAN_COS = 0.999998


def weights(config: dict, seed: int) -> dict:
    """Every row shares an offset, so that every text lies nearer every
    other text than the index's zero-mean filler rows, as trained
    embeddings do."""
    rng = np.random.default_rng(seed)
    return {"table": 1.0 + rng.standard_normal(
        (config["vocab_size"], config["embedding_dim"]), dtype=np.float32)}


def control(params, token_ids: np.ndarray, lengths: np.ndarray,
            config: dict) -> np.ndarray:
    """:func:`embed` over the table rounded to int8 by one scale, the
    nearest precision below the bfloat16 the bag is served in."""
    table = np.asarray(params["table"], dtype=np.float32)
    scale = np.abs(table).max() / 127.0
    return embed({"table": np.round(table / scale) * scale}, token_ids,
                 lengths, config)


def embed(params, token_ids: np.ndarray, lengths: np.ndarray,
          config: dict) -> np.ndarray:
    table = np.asarray(params["table"], dtype=np.float32)
    out = np.zeros((len(token_ids), config["embedding_dim"]), np.float32)
    for i, (ids, n) in enumerate(zip(token_ids, lengths)):
        mean = table[ids[:n]].mean(axis=0)
        out[i] = mean / np.linalg.norm(mean)
    return out
