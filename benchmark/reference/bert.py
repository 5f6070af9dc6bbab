"""Plain reference for the BERT-family embedder and the exact top-k.

Independent of the code under test: the forward pass of BertModel as
published (Devlin et al. 2018; post-layernorm residuals, erf-GELU, learned
absolute positions, token type 0), written in ``jax.numpy`` in float32 at
``highest`` matmul precision, with no kernels, packing or batching tricks.
It takes the program's parameter tree only for its arrays (the names below
are the tree's) and token ids from the program's tokenizer.

Departures from the published model, both the configuration's: the sentence
embedding is the [CLS] state, L2-normalised (BGE's pooling, not BERT's
tanh pooler), and there is no dropout (inference).
"""

from __future__ import annotations

import numpy as np


def _layer_norm(x, scale, bias, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _forward(params, ids, lengths, heads: int, eps: float):
    import jax
    import jax.numpy as jnp

    emb = params["embeddings"]
    n, s = ids.shape
    x = emb["token"][ids] + emb["position"][:s][None] \
        + emb["token_type"][0][None, None]
    x = _layer_norm(x, emb["ln_scale"], emb["ln_bias"], eps)
    keep = jnp.arange(s)[None, :] < lengths[:, None]            # (n, s)
    bias = jnp.where(keep, 0.0, -jnp.inf)[:, None, None, :]
    d = x.shape[-1] // heads
    for layer in params["layers"]:
        a = layer["attn"]
        split = lambda t: t.reshape(n, s, heads, d).transpose(0, 2, 1, 3)
        q = split(x @ a["wq"] + a["bq"])
        k = split(x @ a["wk"] + a["bk"])
        v = split(x @ a["wv"] + a["bv"])
        scores = q @ k.transpose(0, 1, 3, 2) / np.sqrt(d) + bias
        ctx = jax.nn.softmax(scores, axis=-1) @ v               # (n,h,s,d)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(n, s, heads * d)
        x = _layer_norm(x + ctx @ a["wo"] + a["bo"],
                        a["ln_scale"], a["ln_bias"], eps)
        m = layer["mlp"]
        h = jax.nn.gelu(x @ m["w1"] + m["b1"], approximate=False)
        x = _layer_norm(x + h @ m["w2"] + m["b2"],
                        m["ln_scale"], m["ln_bias"], eps)
    cls = x[:, 0]
    return cls / jnp.linalg.norm(cls, axis=-1, keepdims=True)


def embed(params, token_ids: np.ndarray, lengths: np.ndarray, *, heads: int,
          eps: float, batch: int = 128) -> np.ndarray:
    """(n, hidden) float32 unit embeddings of ``token_ids`` (n, s) whose
    first ``lengths[i]`` positions are real tokens ([CLS] first). Rows run
    ``batch`` at a time at one padded shape, so one program serves any n."""
    import jax
    import jax.numpy as jnp

    fwd = jax.jit(lambda p, i, l: _forward(p, i, l, heads, eps))
    ids = np.asarray(token_ids, np.int32)
    lens = np.asarray(lengths, np.int32)
    n = len(ids)
    out = np.zeros((n, int(params["embeddings"]["token"].shape[1])),
                   np.float32)
    with jax.default_matmul_precision("highest"):
        for i in range(0, n, batch):
            b_ids = np.zeros((batch, ids.shape[1]), np.int32)
            b_len = np.ones((batch,), np.int32)
            m = min(batch, n - i)
            b_ids[:m], b_len[:m] = ids[i:i + m], lens[i:i + m]
            out[i:i + m] = np.asarray(
                fwd(params, jnp.asarray(b_ids), jnp.asarray(b_len)))[:m]
    return out


def cosine_scores(queries: np.ndarray, documents: np.ndarray) -> np.ndarray:
    """(n_queries, n_documents) exact float32 cosine similarities by one
    full matmul on the host."""
    q = queries / np.linalg.norm(queries, axis=1, keepdims=True)
    d = documents / np.linalg.norm(documents, axis=1, keepdims=True)
    return q.astype(np.float32) @ d.astype(np.float32).T
