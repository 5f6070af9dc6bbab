"""Plain reference for the BERT-family embedder (``"model": "bert"``).

Every ``benchmark/reference/<model>.py`` defines
``weights(config, seed)``: the model's float32 weights, made here from the
seed and from nothing else (the architecture's ``build`` loads them into the
program; the reference makes them again when it checks, so that nothing the
program did to its copy reaches it);
``embed(params, token_ids, lengths, config) -> (n, dim) float32 unit rows``
over such weights; ``MIN_COS`` and ``MIN_MEAN_COS``, the least cosine a served
embedding may have with the reference's (the worst text's, and the mean),
with the readings they were set from; and ``control``, of ``embed``'s
signature: the same forward pass in the nearest precision below the one the
configuration serves in, which ``correct`` has to refuse
(``benchmark/tools/control.py`` reads both on the chip,
``benchmark/tests/test_control.py`` keeps the control failing).

Independent of the code under test: the forward pass of BertModel as
published (Devlin et al. 2018; post-layernorm residuals, erf-GELU, learned
absolute positions, token type 0), written in ``jax.numpy`` in float32 at
``highest`` matmul precision, with no kernels, packing or batching tricks.
Its weights are its own (:func:`weights`, numpy from the seed; the tree's
names are the program's, so that ``build`` hands the arrays over as they
are); the token ids come from the program's tokenizer.

Departures from the published model, both the configuration's: the sentence
embedding is the [CLS] state, L2-normalised (BGE's pooling, not BERT's
tanh pooler), and there is no dropout (inference).
"""

from __future__ import annotations

import numpy as np

# bf16 vs float32 encoder agreement, as the cosine between the two unit
# embeddings of one text, over the 64 documents a run samples. bfloat16 keeps
# 8 significant bits; over 12 post-LN layers with float32 accumulation and
# float32 layernorm the roundings add like a random walk, and the bf16 path
# also swaps erf-GELU for tanh-GELU (<= 3e-3 abs). Each limit stands between
# two readings of 1 - cos on the chip at the BGE-small shape, over the
# weights :func:`weights` makes (my chip runs, PR 27, second round: 15 seeds
# of ``tools/control.py`` and the runs of 31 more; PERF.md section 2):
#   the mean over the texts: program <= 4.26e-5 (4.25e-5 over the 28 seeds
#     of round one), the int8 ``control`` >= 1.42e-4, 3.3 times apart and
#     steady: limit 1.0e-4. This is the number that holds the control;
#   the worst text: program <= 5.65e-5 (5.76e-5 over round one's 46 seeds),
#     control >= 1.66e-4: 2.9 times apart, short of the three a control's
#     reading needs, as a widest gap swings. The limit, 1.2e-4, refused the
#     control on all 27 seeds read all the same, but what it is there for
#     is one text gone wrong: a wrong mask, a dropped layer, a document
#     attending its neighbour in a packed sequence, an embedding altered
#     where it is produced all land under 0.99, since two different
#     documents are only 0.99 alike.
# int8 with a scale for each token and channel (``int8_channel``) reads
# 6.1-7.3e-5 in the mean and 6.9-9.0e-5 in the worst text: 1.5 times the
# program's own bfloat16, under both limits, and no cosine will tell the two
# apart; fp8 reads 5.2e-4 or more either way and is refused.
MIN_COS = 0.99988
MIN_MEAN_COS = 0.9999


def _layer_norm(x, scale, bias, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def weights(config: dict, seed: int) -> dict:
    """The float32 weights of the configuration's model from ``seed``, as
    BertModel initialises them (normal of deviation 0.02 for every matrix
    and table, zero biases, unit layernorm), by numpy's generator in the
    order written here. A new tree at every call."""
    rng = np.random.default_rng(seed)
    h, f = config["hidden_size"], config["intermediate_size"]

    def dense(*shape):
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(0.02)

    def norm():
        return {"ln_scale": np.ones(h, np.float32),
                "ln_bias": np.zeros(h, np.float32)}

    def linear(name, rows, cols):
        return {"w" + name: dense(rows, cols),
                "b" + name: np.zeros(cols, np.float32)}

    return {
        "embeddings": {"token": dense(config["vocab_size"], h),
                       "position": dense(config["max_position_embeddings"],
                                         h),
                       "token_type": dense(config["type_vocab_size"], h),
                       **norm()},
        "layers": [{"attn": {**linear("q", h, h), **linear("k", h, h),
                             **linear("v", h, h), **linear("o", h, h),
                             **norm()},
                    "mlp": {**linear("1", h, f), **linear("2", f, h),
                            **norm()}}
                   for _ in range(config["num_hidden_layers"])]}


def _quantized_matmul(bits: str, by_channel: bool):
    """``a @ b`` as a product of 8-bit operands gives it: each operand
    scaled to the type's range and rounded to it, the sum kept wide.
    ``bits`` is ``int8`` or ``fp8`` (e4m3). One scale a tensor is what a PR
    writes first and the chip runs fastest; ``by_channel`` takes one for
    each token of ``a`` and each output channel of ``b`` (over the
    contracted axis), the most careful 8 bits there are."""
    import jax.numpy as jnp

    top = 127.0 if bits == "int8" else 448.0

    def scale(t, axis):
        s = jnp.max(jnp.abs(t), axis=axis if by_channel else None,
                    keepdims=by_channel) / top
        return jnp.where(s > 0, s, 1.0)

    def mm(a, b):
        sa, sb = scale(a, -1), scale(b, -2)
        if bits == "int8":
            qa = jnp.round(a / sa).astype(jnp.int8)
            qb = jnp.round(b / sb).astype(jnp.int8)
            out = jnp.matmul(qa, qb, preferred_element_type=jnp.int32)
        else:
            qa = (a / sa).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            qb = (b / sb).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            out = jnp.matmul(qa, qb)
        return out.astype(jnp.float32) * (sa * sb)

    return mm


#: the lower precisions :func:`control` can compute in. The first is *the*
#: control, which ``correct`` has to refuse; the tool reads the others
#: beside it, and PERF.md section 2 says which of them no cosine refuses
CONTROL_KINDS = ("int8", "int8_channel", "fp8", "fp8_channel")


def _forward(params, ids, lengths, heads: int, eps: float, mm=None):
    """``mm`` stands in for every matrix product (the control's)."""
    import jax
    import jax.numpy as jnp

    if mm is None:
        mm = jnp.matmul

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
        q = split(mm(x, a["wq"]) + a["bq"])
        k = split(mm(x, a["wk"]) + a["bk"])
        v = split(mm(x, a["wv"]) + a["bv"])
        scores = mm(q, k.transpose(0, 1, 3, 2)) / np.sqrt(d) + bias
        ctx = mm(jax.nn.softmax(scores, axis=-1), v)             # (n,h,s,d)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(n, s, heads * d)
        x = _layer_norm(x + mm(ctx, a["wo"]) + a["bo"],
                        a["ln_scale"], a["ln_bias"], eps)
        m = layer["mlp"]
        h = jax.nn.gelu(mm(x, m["w1"]) + m["b1"], approximate=False)
        x = _layer_norm(x + mm(h, m["w2"]) + m["b2"],
                        m["ln_scale"], m["ln_bias"], eps)
    cls = x[:, 0]
    return cls / jnp.linalg.norm(cls, axis=-1, keepdims=True)


def embed(params, token_ids: np.ndarray, lengths: np.ndarray,
          config: dict) -> np.ndarray:
    """(n, hidden) float32 unit embeddings of ``token_ids`` (n, s) whose
    first ``lengths[i]`` positions are real tokens ([CLS] first), by the
    configuration's published fields."""
    return _embed(params, token_ids, lengths,
                  heads=config["num_attention_heads"],
                  eps=config["layer_norm_eps"])


def control(params, token_ids: np.ndarray, lengths: np.ndarray,
            config: dict, kind: str = CONTROL_KINDS[0]) -> np.ndarray:
    """:func:`embed` with every matrix product (the dense layers' and
    attention's two) in 8 bits, the nearest precision below the bfloat16 the
    configuration serves in and the one the chip has units for; layernorm,
    softmax, GELU and the accumulation stay as they are. What a later PR
    that served in int8 would produce at best: ``correct`` has to refuse
    it. ``kind`` is one of :data:`CONTROL_KINDS`."""
    bits, _sep, by_channel = kind.partition("_")
    return _embed(params, token_ids, lengths,
                  heads=config["num_attention_heads"],
                  eps=config["layer_norm_eps"],
                  mm=_quantized_matmul(bits, bool(by_channel)))


def _embed(params, token_ids: np.ndarray, lengths: np.ndarray, *, heads: int,
           eps: float, batch: int = 128, mm=None) -> np.ndarray:
    """Rows run ``batch`` at a time at one padded shape, so one program
    serves any n. Under ``mm`` the last rows run unpadded: a scale taken
    over a tensor must see the texts alone."""
    import jax
    import jax.numpy as jnp

    fwd = jax.jit(lambda p, i, l: _forward(p, i, l, heads, eps, mm))
    params = jax.device_put(params)       # once, not at every block
    ids = np.asarray(token_ids, np.int32)
    lens = np.asarray(lengths, np.int32)
    n = len(ids)
    out = np.zeros((n, int(params["embeddings"]["token"].shape[1])),
                   np.float32)
    with jax.default_matmul_precision("highest"):
        for i in range(0, n, batch):
            m = min(batch, n - i)
            rows = batch if mm is None else m
            b_ids = np.zeros((rows, ids.shape[1]), np.int32)
            b_len = np.ones((rows,), np.int32)
            b_ids[:m], b_len[:m] = ids[i:i + m], lens[i:i + m]
            out[i:i + m] = np.asarray(
                fwd(params, jnp.asarray(b_ids), jnp.asarray(b_len)))[:m]
    return out
