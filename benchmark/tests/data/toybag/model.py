"""A second architecture, for the rehearsal alone (``"model": "toybag"``):
a mean-pooled embedding bag. The test copies this file to
``benchmark/models/toybag.py`` under a temporary root; nothing of
``benchmark/lib/`` knows it. What it shares with BERT is the program's
embedder protocol (benchmark/README.md) and nothing else: its own
tokenizer, packer, forward pass and costs.
"""

from __future__ import annotations

import numpy as np

from pathway_tpu.xpacks.llm.embedders import BaseEmbedder


class WordTokenizer:
    """``word<i>`` -> i + 1; 0 pads. The mixes' vocabulary, whole."""

    def batch(self, texts: list[str], max_len: int
              ) -> tuple[np.ndarray, np.ndarray]:
        rows = [[int(w[4:]) + 1 for w in t.split()[:max_len]
                 if w.startswith("word") and w[4:].isdigit()] or [0]
                for t in texts]
        ids = np.zeros((len(rows), max(map(len, rows))), np.int32)
        mask = np.zeros(ids.shape, bool)
        for i, row in enumerate(rows):
            ids[i, :len(row)], mask[i, :len(row)] = row, True
        return ids, mask


class BagEmbedder(BaseEmbedder):
    """Unit mean of the table's rows of a text's words, in bfloat16."""

    ragged = False

    def __init__(self, table, max_len: int):
        super().__init__(batch=True, deterministic=True, device=True)
        import jax

        self.params = {"table": table}
        self.tokenizer = WordTokenizer()
        self.max_len = max_len
        self._encode = jax.jit(self.device_producer)

    def pack_tokens(self, texts: list[str]) -> tuple[np.ndarray, np.ndarray]:
        ids, mask = self.tokenizer.batch([t or "." for t in texts],
                                         max_len=self.max_len)
        ids = np.pad(ids, ((0, 0), (0, self.max_len - ids.shape[1])))
        return ids, np.maximum(mask.sum(axis=1), 1).astype(np.int32)

    def device_producer(self, params, ids, lens):
        import jax
        import jax.numpy as jnp

        with jax.named_scope("toybag.embed"):
            rows = params["table"][ids]                       # (B, W, dim)
        with jax.named_scope("toybag.pool"):
            keep = jnp.arange(ids.shape[1])[None, :] < lens[:, None]
            mean = jnp.sum(jnp.where(keep[..., None], rows, 0), axis=1,
                           dtype=jnp.float32) / lens[:, None]
            return mean / jnp.linalg.norm(mean, axis=-1, keepdims=True)

    def encode_batch_device(self, texts: list[str]):
        import jax.numpy as jnp

        ids, lens = self.pack_tokens(texts)
        return self._encode(self.params, jnp.asarray(ids), jnp.asarray(lens))

    def __wrapped__(self, texts: list[str], **kwargs) -> list[np.ndarray]:
        return list(np.asarray(self.encode_batch_device(list(texts))))

    def get_embedding_dimension(self, **kwargs) -> int:
        return int(self.params["table"].shape[1])


def build(config: dict, weights: dict):
    import jax.numpy as jnp

    table = jnp.asarray(weights["table"],
                        getattr(jnp, config["serving"]["compute_dtype"]))
    return BagEmbedder(table, config["serving"]["max_len"])


def tokens(embedder, config: dict, texts: list[str]
           ) -> tuple[np.ndarray, np.ndarray]:
    return embedder.pack_tokens(texts)


def dispatch_cost(config: dict, shape: tuple, ragged: bool
                  ) -> tuple[float, float]:
    """One add a gathered element; the gathered rows read once, one row a
    text written."""
    rows, width = shape
    dim = config["embedding_dim"]
    return float(rows * width * dim), float(2 * rows * (width + 1) * dim)
