"""Plain reference for the decoder with window and full attention mixed and
ReGLU routed experts, used as an embedder (``"model": "smallthinker"``).

Independent of the code under test (it imports nothing of ``pathway_tpu``):
the layer equations of SmallThinker as its ``config.json`` and the family's
description give them (``PowerInfer/SmallThinker-21BA3B-Instruct``), in
``jax.numpy`` float32 at ``highest`` matmul precision, with no kernel, no
packing and no batching: one document at a time, **attention by the full
mask of one block of 256 queries at a time** against every key of the
document (at 16,384 keys and 28 heads that block's scores are 0.47 GB in
float32: the whole document's would be 30), **every expert applied to every
token** and weighted by a mask that is zero where the router did not choose
it. One layer's float32 weights (1.6 GB at the published widths) are on the
device at a time, so that the reference fits beside the program it checks.

The layer (all norms RMSNorm with a plain weight, ``x / rms(x) * w``; no
biases)::

    h = norm1(x)
    q = h Wq (heads x head_dim); k = h Wk, v = h Wv (key heads x head_dim)
    rope_layout[i] = 1: q, k rotated over the whole head (half-split, theta)
    rope_layout[i] = 0: no positions at all
    visible(t, s) = s <= t and (sliding_window_layout[i] = 0
                                or t - s < sliding_window_size)
    x = x + softmax(q k^T / sqrt(head_dim) over visible) v Wo
    m = norm2(x)
    p = softmax(h Wr) over all experts; the moe_num_active_primary_experts
        largest, renormalised to sum 1
    x = x + sum_e p_e Wdown_e (relu(Wgate_e m) * (Wup_e m))

and the embedding is the final norm's state of the last token,
L2-normalised.

**Departures and readings** (the configuration's ``assumed`` lists them
too): the router reads ``h``, the normed input of attention (the family is
described as "router placed before attention"; that it is the normed state
and not ``x`` is this reference's reading); the window keeps ``t - s <
sliding_window_size``, so the key at distance 4,096 is out; the secondary
experts the family's description mentions are off (the config has no key
for them); ``lm_head`` takes no part in an embedding and is absent; token
ids are the program's WordPiece ids.

**Host memory.** :func:`weights` makes 1.98 billion float32 numbers at the
published cut: 7.9 GB, made once at build and again at the check.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# bf16 vs float32 agreement, as the cosine between the two unit embeddings
# of one text, over the 64 documents a run samples. Each limit stands
# between two readings of 1 - cos on the chip at the published widths (my
# chip runs, PR 33: the runs' own lines on seven seeds, and the program
# beside the int8 ``control`` on two more, one process a seed, 64 documents
# each; PERF.md section 2):
#   the mean over the texts: program 1.9e-4 to 4.8e-4, the int8 control
#     1.28e-2 and 1.35e-2, twenty-seven times apart at the nearest: limit
#     2e-3, 4.2 times the program's largest and 6.4 times under the
#     control's smallest. This is the number that holds the control;
#   the worst text: program 2.9e-3 to 8.4e-3 (a long document: the router
#     is float32 in program and reference alike, and a bf16 rounding
#     upstream of it swaps the sixth of 64 nearly level probabilities for
#     the seventh in some token; with every expert held and plain norms a
#     swap moves a state far less than in ``reference/qwen3_next.py``,
#     whose program reads 1e-2 in the mean; how many tokens swap is not
#     measured). What the limit is there for is one text gone wrong (a
#     document attending its neighbour in a packed row, a window cut one
#     key short, a wrong pooled token): two different documents' served
#     embeddings are 0.17 alike in the mean and 0.73 at most (64 documents,
#     seed 3300012), so a wrong text reads 0.27 or more. Limit 2.5e-2:
#     three times the program's largest, a tenth of a wrong text's least.
#     The control reads 3.4e-2 on both seeds, 1.4 times over: refused by
#     this number too, though it is not its upper reading.
MIN_COS = 0.975
MIN_MEAN_COS = 0.998

#: the lower precisions :func:`control` can compute in; the first is *the*
#: control, which ``correct`` has to refuse
CONTROL_KINDS = ("int8",)

_THREADS = min(8, os.cpu_count() or 1)
#: numbers a generator of its own draws: a fixed cut, so that the values
#: depend on the seed alone and not on the threads that drew them
_BLOCK = 1 << 24
#: queries whose scores are held at once
QUERY_BLOCK = 256
#: a document is computed at the least of these lengths that holds it (and
#: beyond them at the next multiple of 4,096): seven shapes to compile at
#: the published context, a quarter more slots than tokens over documents
#: spread evenly in length; what lies behind a document's last token a
#: causal model never reads
_LENGTHS = (512, 1024, 2048, 4096, 8192, 12288, 16384)


def _sizes(config: dict) -> dict:
    c = config
    return dict(
        h=c["hidden_size"], experts=c["moe_num_primary_experts"],
        k=c["moe_num_active_primary_experts"], f=c["moe_ffn_hidden_size"],
        nh=c["num_attention_heads"], nkv=c["num_key_value_heads"],
        hd=c["head_dim"], theta=float(c["rope_theta"]),
        window=c["sliding_window_size"], eps=c["rms_norm_eps"],
        renormalise=bool(c["norm_topk_prob"]))


def weights(config: dict, seed: int) -> dict:
    """The float32 weights of the configuration's model from ``seed``, in
    the program's tree: every matrix and table normal of deviation 0.02,
    every norm's weight one. Each block of 2**24 numbers has a generator of
    its own, seeded by (seed, tensor, block), so threads draw them side by
    side and the values depend on the seed alone. A new tree at every
    call."""
    s = _sizes(config)
    h, e, f = s["h"], s["experts"], s["f"]
    jobs, counter = [], [0]

    def dense(*shape):
        out = np.empty(shape, np.float32)
        flat, tensor = out.reshape(-1), counter[0]
        counter[0] += 1
        jobs.extend((flat[i:i + _BLOCK], (seed, tensor, i // _BLOCK))
                    for i in range(0, flat.size, _BLOCK))
        return out

    def ones(n):
        return np.ones(n, np.float32)

    layers = []
    for _ in range(config["num_hidden_layers"]):
        layers.append({
            "norm1": ones(h), "norm2": ones(h),
            "mixer": {"q_proj": dense(h, s["nh"] * s["hd"]),
                      "k_proj": dense(h, s["nkv"] * s["hd"]),
                      "v_proj": dense(h, s["nkv"] * s["hd"]),
                      "o_proj": dense(s["nh"] * s["hd"], h)},
            "moe": {"router": dense(h, e), "gate": dense(e, h, f),
                    "up": dense(e, h, f), "down": dense(e, f, h)}})
    params = {"embed": dense(config["vocab_size"], h), "layers": layers,
              "final_norm": ones(h)}

    def draw(job):
        view, key = job
        np.random.default_rng(key).standard_normal(
            view.shape, dtype=np.float32, out=view)
        view *= np.float32(0.02)

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(draw, jobs))
    return params


# -- the layers, one document at a time ---------------------------------------

def _int8_matmul(a, b):
    """``a @ b`` as a product of int8 operands gives it: each operand scaled
    to the type's range by one scale a tensor and rounded, the sum kept
    wide."""
    import jax.numpy as jnp

    def quantise(t):
        s = jnp.max(jnp.abs(t)) / 127.0
        s = jnp.where(s > 0, s, 1.0)
        return jnp.round(t / s).astype(jnp.int8), s

    (qa, sa), (qb, sb) = quantise(a), quantise(b)
    out = jnp.matmul(qa, qb, preferred_element_type=jnp.int32)
    return out.astype(jnp.float32) * (sa * sb)


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _attention(x, p, s, window, rotary, mm):
    """x (T, H) normed -> (T, H): causal attention of one document, a
    block of queries at a time against every key under the full mask.
    ``window``: the keys a query keeps (the document's length or more: all
    of them); ``rotary``: 1.0 where q and k are rotated, 0.0 where the
    layer has no positions (every angle is then zero: the identity). Both
    are numbers and not switches, so that one compiled function serves both
    kinds of layer at a length."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    nh, nkv, hd = s["nh"], s["nkv"], s["hd"]
    q = mm(x, p["q_proj"]).reshape(t, nh, hd)
    k = mm(x, p["k_proj"]).reshape(t, nkv, hd)
    v = mm(x, p["v_proj"]).reshape(t, nkv, hd)
    half = hd // 2
    freq = s["theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = rotary * jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    turn = lambda a: jnp.concatenate(
        [a[..., :half] * cos - a[..., half:] * sin,
         a[..., half:] * cos + a[..., :half] * sin], axis=-1)
    q, k = turn(q), turn(k)
    q = q.transpose(1, 0, 2)                                  # (nh, T, hd)
    k, v = (jnp.repeat(a.transpose(1, 0, 2), nh // nkv, axis=0)
            for a in (k, v))
    keys = jnp.arange(t)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, QUERY_BLOCK, axis=1)
        at = start + jnp.arange(QUERY_BLOCK)
        see = (keys[None, :] <= at[:, None]) \
            & (at[:, None] - keys[None, :] < window)
        scores = mm(qb, k.transpose(0, 2, 1)) * hd ** -0.5    # (nh, Q, T)
        probs = jax.nn.softmax(jnp.where(see, scores, -jnp.inf), axis=-1)
        return mm(probs, v)                                   # (nh, Q, hd)

    o = jax.lax.map(block, jnp.arange(0, t, QUERY_BLOCK))     # (n,nh,Q,hd)
    o = o.transpose(0, 2, 1, 3).reshape(t, nh * hd)
    return mm(o, p["o_proj"])


def _moe(x, routed, p, s, mm):
    """x (T, H) -> (T, H): every expert over every token, weighted by the
    router's choice (zero where it chose another). ``routed`` (T, H) is
    what the router reads."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(jnp.matmul(routed, p["router"]), axis=-1)
    top, chosen = jax.lax.top_k(probs, s["k"])
    if s["renormalise"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    weight = jnp.sum(jnp.where(
        chosen[:, :, None] == jnp.arange(s["experts"])[None, None, :],
        top[:, :, None], 0.0), axis=1)                        # (T, experts)

    def expert(y, xs):
        w_gate, w_up, w_down, w = xs
        out = mm(jax.nn.relu(mm(x, w_gate)) * mm(x, w_up), w_down)
        return y + w[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (p["gate"], p["up"], p["down"], weight.T))
    return y


def _layer(x, p, s, window, rotary, mm):
    h = _rms_norm(x, p["norm1"], s["eps"])
    x = x + _attention(h, p["mixer"], s, window, rotary, mm)
    return x + _moe(_rms_norm(x, p["norm2"], s["eps"]), h, p["moe"], s, mm)


def _padded_length(n: int) -> int:
    for length in _LENGTHS:
        if n <= length:
            return length
    return -(-n // 4096) * 4096


def _embed(params, token_ids, lengths, config: dict, mm) -> np.ndarray:
    """Layer by layer (one layer's weights on the device at a time), one
    document at a time at the least of a few lengths that holds it: the
    model is causal, so what lies behind a document's last token does not
    reach it."""
    import jax
    import jax.numpy as jnp

    s = _sizes(config)
    ids = np.asarray(token_ids, np.int32)
    lens = np.maximum(np.asarray(lengths, np.int64), 1)
    table = np.asarray(params["embed"], np.float32)
    states = []
    for row, n in zip(ids, lens):
        padded = np.zeros(_padded_length(int(n)), np.int32)
        padded[:n] = row[:n]
        states.append(table[padded])                          # (T, H)
    layer_fn = jax.jit(lambda p, x, window, rotary: _layer(
        x, p, s, window, rotary, mm))
    with jax.default_matmul_precision("highest"):
        for i, layer in enumerate(params["layers"]):
            on_device = jax.device_put(layer)
            for d, x in enumerate(states):
                # a layer without a window keeps every key of the document
                window = s["window"] if config["sliding_window_layout"][i] \
                    else len(x)
                states[d] = np.asarray(layer_fn(
                    on_device, jnp.asarray(x), jnp.int32(window),
                    jnp.float32(config["rope_layout"][i])))
            del on_device
        last = np.stack([x[n - 1] for x, n in zip(states, lens)])
        last = np.asarray(_rms_norm(jnp.asarray(last),
                                    jnp.asarray(params["final_norm"]),
                                    s["eps"]))
    return last / np.linalg.norm(last, axis=-1, keepdims=True)


def embed(params, token_ids: np.ndarray, lengths: np.ndarray,
          config: dict) -> np.ndarray:
    """(n, hidden) float32 unit embeddings of ``token_ids`` (n, S) whose
    first ``lengths[i]`` positions are real tokens."""
    import jax.numpy as jnp

    return _embed(params, token_ids, lengths, config, jnp.matmul)


def control(params, token_ids: np.ndarray, lengths: np.ndarray,
            config: dict, kind: str = CONTROL_KINDS[0]) -> np.ndarray:
    """:func:`embed` with every product of attention (its projections and
    its two products) and of the experts in int8, one scale a tensor (an
    expert's matrix is a tensor of its own, as checkpoints keep it): the
    nearest precision below the bfloat16 the configuration serves in. The
    router, the norms and the softmax stay float32, as the configuration's
    ``serving`` keeps them: what a later PR that served in int8 would
    produce at best, and ``correct`` has to refuse it."""
    if kind not in CONTROL_KINDS:
        raise ValueError(f"unknown control {kind!r}")
    return _embed(params, token_ids, lengths, config, _int8_matmul)
