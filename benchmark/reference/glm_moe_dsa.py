"""Plain reference for the decoder of latent attention over a learned choice
of keys, a dense or an expert feed-forward a layer, a sigmoid router and an
ungated shared expert, used as an embedder (``"model": "glm_moe_dsa"``).

Independent of the code under test (it imports nothing of ``pathway_tpu``;
what it shares with ``benchmark/reference/longcat_flash.py`` is that file's
plain helpers: the seeded draws, the norm, the rotary turn, the int8
product, the loops over blocks): the layer equations of GLM-5.2's language
model as its ``config.json`` gives them (``zai-org/GLM-5.2``), in
``jax.numpy`` float32 at ``highest`` matmul precision, with no kernel, no
packing and no batching: one document at a time, **the indexer's scores of
one block of 256 queries against every key of the document, its choice by
``jax.lax.top_k`` over the masked scores**, attention by the mask of that
choice, eight heads' keys and values expanded at a time (the unabsorbed
form), **every held expert applied to every token** and weighted by a mask
that is zero where the router did not choose it.

The layer ``l`` (all norms RMSNorm with a plain weight, ``x / rms(x) * w``,
eps 1e-5; no biases but the index key's LayerNorm; rotary on neighbouring
pairs)::

    a    = norm1(x)
    c_q  = norm_q(a W_qa)                         q = c_q W_qb -> heads x (nope | rope), rotary on the rope part
    kv   = a W_kva -> (kv_lora_rank | rope)       c_kv = norm_kv(kv[:rank]);  k_r = rotary(kv[rank:]), one a token
    [k_nope | v] = c_kv W_kvb -> heads x (nope | v_head_dim)
    where indexer_types[l] == "full":
      qI = c_q WI_qb -> index heads x index_head_dim, rotary on each head's first rope features
      kI = LayerNorm(a WI_k), rotary on its first rope features
      wI = (a WI_w) * index_n_heads ** -0.5 * index_head_dim ** -0.5
      I[t, s] = sum_j wI[t, j] relu(qI[t, j] . kI[s])        for every s <= t
      S[t] = every s <= t where they are at most index_topk, else the
             index_topk of largest I[t, .] (top_k: a tie goes to the earlier)
    where it is "shared": S is that of the nearest "full" layer before l
    o[t, h] = sum over s in S[t] of softmax_s(q[t, h] . k[s, h] (nope + rope) ** -0.5) v[s, h]
    x = x + concat_h(o) W_o;   b = norm2(x)
    mlp_layer_types[l] == "dense":   x = x + W_down(silu(W_gate b) * (W_up b))
                       == "sparse":  p = sigmoid(b W_r);  E = the k largest of p + bias
                                     g = p[E] / sum(p[E]) * routed_scaling_factor
                                     x = x + sum over held e in E of g_e Expert_e(b) + Shared(b)

and the embedding is the final norm's state of the last token,
L2-normalised.

**The share.** The configuration holds a range of the routed experts
(``experts_held`` of ``published.n_routed_experts``) and a range of the
published layers (``layers_held`` of ``mlp_layer_types`` and
``indexer_types``, which keep every published entry): the router keeps every
output and renormalises over all it chose, and only held experts and the
shared one add. What the absent experts would have added is left out, here
as in the program.

**Two controls** (:func:`control`): ``"int8"``, every product of attention,
indexer, feed-forwards and experts in int8 with one scale a tensor (the
nearest precision below the bfloat16 the configuration serves in), and
``"dense"``, this reference in float32 with the choice left out: every
visible key attended. ``correct`` has to refuse both.

**Memory and time.** As ``longcat_flash.py``: a layer's weights are made
when it is asked for (3.3 GB in float32 at the published cut) and go to the
device a part at a time (the attention, the dense feed-forward, eight
experts); the documents' states lie one behind the other in a buffer (a
*wave*), and **a part is one call a wave**, because the program under test
keeps ingesting on the same chip and every call queues behind its
dispatches. The device walks a wave's documents itself, each in a window as
long as the longest sampled document (rounded up to 1,024 tokens). A full
layer's choices are kept a bit a pair (8.5 MB a document of 8,194 tokens)
for the shared layers behind it.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.reference.longcat_flash import (
    _ALIGN, _BLOCK, HEAD_GROUP, QUERY_BLOCK, TOKEN_BLOCK, _by_tokens, _draw,
    _each, _ffn, _int8_matmul, _rms_norm, _turn, _waves)

# bf16 vs float32 agreement, as the cosine between the two unit embeddings
# of one text, over the 8 documents a run samples. Each limit stands between
# readings of 1 - cos on the chip at the published widths (my chip runs,
# PR 40: the runs' own lines, and the program beside both controls on three
# more seeds, one process a seed, 8 documents each; PERF.md section 2 has
# every reading):
#   the mean over the texts: program 3.4e-3 to 6.4e-3 (five seeds; a text
#     in twenty reads 1e-2 to 3e-2, which a mean over 8 carries), the int8
#     control 3.31e-2 to 3.51e-2 (steady: three seeds), the control that
#     attends over every visible key 0.423 to 0.429: limit 1.5e-2, 2.4 times
#     the program's largest and 2.2 times under the int8 control's
#     smallest. This is the number that holds the int8 control;
#   the worst text: program 5.9e-3 to 3.0e-2, the int8 control 4.2e-2 to
#     5.7e-2 (1.4 times the program's largest: not this number's upper
#     reading), the dense control 0.59 to 0.66. Limit 0.15: five times the
#     program's largest, a quarter of the dense control's smallest; a text
#     gone wrong (a document attending its neighbour in a packed row, a
#     wrong pooled token) reads as two different documents do.
MIN_COS = 0.85
MIN_MEAN_COS = 0.985

#: what :func:`control` can compute in the reference's place; ``correct`` has
#: to refuse every one. The first is the lower precision, the second the
#: reference's own precision with the choice of keys left out
CONTROL_KINDS = ("int8", "dense")

#: index heads whose scores of a block of queries are held at once (at 9,216
#: keys a block's scores of eight heads are 75 MB in float32)
INDEX_HEAD_GROUP = 8
#: tokens of a wave at the most (1.6 GB of float32 states at 6,144 features)
WAVE_TOKENS = 65536
#: float32 bytes of experts on the device at a time
_EXPERT_BYTES = 1.3e9
#: a layer's tensors are numbered from ``1 + layer * _TENSORS_A_LAYER``: the
#: layer's published number, so a layer's weights are its own whichever
#: range of layers is held
_TENSORS_A_LAYER = 32
_LAYER_NORM_EPS = 1e-6


def _sizes(config: dict) -> dict:
    c = config
    first, last = c["layers_held"]
    return dict(
        h=c["hidden_size"], nh=c["num_attention_heads"],
        q_rank=c["q_lora_rank"], kv_rank=c["kv_lora_rank"],
        dn=c["qk_nope_head_dim"], dr=c["qk_rope_head_dim"],
        dv=c["v_head_dim"], ffn=c["intermediate_size"],
        f=c["moe_intermediate_size"], shared=c["n_shared_experts"],
        experts=c["published"]["n_routed_experts"],
        k=c["num_experts_per_tok"],
        scale=float(c["routed_scaling_factor"]),
        held=tuple(c["experts_held"]),
        ni=c["index_n_heads"], di=c["index_head_dim"],
        topk=c["index_topk"], first_layer=first,
        mlp=tuple(c["mlp_layer_types"][first:last]),
        indexers=tuple(c["indexer_types"][first:last]),
        theta=float(c["rope_parameters"]["rope_theta"]),
        eps=c["rms_norm_eps"])


class _Layers:
    """The held layers' float32 weights, each made from the seed when it is
    asked for and not kept: ``layers[i]`` is a new tree at every asking
    (3.3 GB at the published cut)."""

    def __init__(self, config: dict, seed: int):
        self.config, self.seed = config, seed

    def __len__(self) -> int:
        return self.config["num_hidden_layers"]

    def __iter__(self):
        return (self[i] for i in range(len(self)))

    def __getitem__(self, layer: int) -> dict:
        if not 0 <= layer < len(self):
            raise IndexError(layer)
        s, seed = _sizes(self.config), self.seed
        h, nh = s["h"], s["nh"]
        jobs = []
        tensor = [1 + (s["first_layer"] + layer) * _TENSORS_A_LAYER]

        def dense(*shape, deviation=0.02, first=0):
            """``first``: the number of the first of ``shape[0]`` tensors
            that are drawn one by one (an expert's matrix is its own,
            whichever range of them is held)."""
            out, t = np.empty(shape, np.float32), tensor[0]
            tensor[0] += 1
            parts = out if len(shape) == 3 else out[None]
            for e, part in enumerate(parts):
                flat = part.reshape(-1)
                jobs.extend(
                    (flat[i:i + _BLOCK], (seed, t, first + e, i // _BLOCK),
                     deviation) for i in range(0, flat.size, _BLOCK))
            return out

        ones = lambda n: np.ones(n, np.float32)
        mixer = {"q_a": dense(h, s["q_rank"]), "q_norm": ones(s["q_rank"]),
                 "q_b": dense(s["q_rank"], nh * (s["dn"] + s["dr"])),
                 "kv_a": dense(h, s["kv_rank"] + s["dr"]),
                 "kv_norm": ones(s["kv_rank"]),
                 "kv_b": dense(s["kv_rank"], nh * (s["dn"] + s["dv"])),
                 "o": dense(nh * s["dv"], h)}
        # every layer numbers an indexer's and both feed-forwards' tensors,
        # so that a tensor's number says what it is in every layer
        at = tensor[0]
        if s["indexers"][layer] == "full":
            mixer["indexer"] = {
                "q_b": dense(s["q_rank"], s["ni"] * s["di"]),
                "k": dense(h, s["di"]), "k_norm": ones(s["di"]),
                "k_bias": np.zeros(s["di"], np.float32),
                "w": dense(h, s["ni"])}
        tensor[0] = at + 3
        tree = {"norm1": ones(h), "norm2": ones(h), "mixer": mixer}
        if s["mlp"][layer] == "dense":
            tree["ffn"] = {"gate": dense(h, s["ffn"]),
                           "up": dense(h, s["ffn"]),
                           "down": dense(s["ffn"], h)}
        else:
            tensor[0] += 3
            lo, hi = s["held"]
            wide = s["shared"] * s["f"]
            tree["moe"] = {
                "router": dense(h, s["experts"]),
                "bias": dense(s["experts"], deviation=0.001),
                "gate": dense(hi - lo, h, s["f"], first=lo),
                "up": dense(hi - lo, h, s["f"], first=lo),
                "down": dense(hi - lo, s["f"], h, first=lo),
                "shared_gate": dense(h, wide), "shared_up": dense(h, wide),
                "shared_down": dense(wide, h)}
        _draw(jobs)
        return tree


def weights(config: dict, seed: int) -> dict:
    """The float32 weights of the configuration's model from ``seed``, in
    the program's tree: every matrix and table normal of deviation 0.02,
    the router's correction bias 0.001, every norm's weight one, the index
    key's LayerNorm weight one and bias zero. Each block of 2**24 numbers
    has a generator of its own, seeded by (seed, tensor, expert, block), so
    threads draw them side by side and the values depend on the seed alone;
    a layer's tensors are numbered from its published number and an
    expert's by its own, whichever ranges are held. ``"layers"`` makes a
    layer when it is indexed (:class:`_Layers`); ``dict(w,
    layers=list(w["layers"]))`` is the whole tree, for a model small enough
    to hold."""
    table = np.empty((config["vocab_size"], config["hidden_size"]),
                     np.float32)
    flat = table.reshape(-1)
    _draw([(flat[i:i + _BLOCK], (seed, 0, 0, i // _BLOCK), 0.02)
           for i in range(0, flat.size, _BLOCK)])
    return {"embed": table, "layers": _Layers(config, seed),
            "final_norm": np.ones(config["hidden_size"], np.float32)}


# -- the layers, one document at a time ---------------------------------------

def _layer_norm(x, w, b):
    import jax.numpy as jnp

    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                        + _LAYER_NORM_EPS) * w + b


def _turn_first(x, at, theta, rot: int):
    """:func:`_turn` over the first ``rot`` features of the last axis."""
    import jax.numpy as jnp

    return jnp.concatenate([_turn(x[..., :rot], at, theta), x[..., rot:]],
                           axis=-1)


def _choose(a, q_latent, p, s, mm, n):
    """The indexer's choice for one document, (T, T) bool: ``a`` (T, H) the
    layer's normed input, ``q_latent`` (T, q_rank) the queries' normed
    latent, ``p`` the indexer's weights; a block of queries at a time, a
    group of index heads' scores at a time, ``jax.lax.top_k`` over the
    scores with what a query does not see at minus infinity. With ``n`` the
    blocks behind the first ``n`` tokens are left false."""
    import jax
    import jax.numpy as jnp

    t = a.shape[0]
    ni, di, rot = s["ni"], s["di"], s["dr"]
    at = jnp.arange(t)
    queries = math.gcd(t, QUERY_BLOCK)
    heads = math.gcd(ni, INDEX_HEAD_GROUP)
    wanted = min(s["topk"], t)

    def inputs(xb):
        ab, qb = xb[:, :a.shape[1]], xb[:, a.shape[1]:]
        return jnp.concatenate(
            [mm(qb, p["q_b"]), _layer_norm(mm(ab, p["k"]), p["k_norm"],
                                           p["k_bias"]),
             mm(ab, p["w"]) * (ni ** -0.5 * di ** -0.5)], axis=-1)

    made = _by_tokens(inputs, jnp.concatenate([a, q_latent], axis=-1), n)
    q = _turn_first(made[:, :ni * di].reshape(t, ni, di), at, s["theta"], rot)
    k = _turn_first(made[:, ni * di:ni * di + di], at, s["theta"], rot)
    w = made[:, ni * di + di:]                                    # (T, ni)

    def block(start):
        cut = lambda x: jax.lax.dynamic_slice_in_dim(x, start, queries)
        here = start + jnp.arange(queries)

        def group(scores, j):
            qj = jax.lax.dynamic_slice_in_dim(cut(q), j * heads, heads, 1)
            wj = jax.lax.dynamic_slice_in_dim(cut(w), j * heads, heads, 1)
            sj = mm(qj.transpose(1, 0, 2), k.T)               # (heads, Q, T)
            return scores + jnp.einsum("jqk,qj->qk", jax.nn.relu(sj),
                                       wj), None

        scores, _ = jax.lax.scan(group, jnp.zeros((queries, t)),
                                 jnp.arange(ni // heads))
        see = at[None, :] <= here[:, None]                        # (Q, T)
        _, best = jax.lax.top_k(jnp.where(see, scores, -jnp.inf), wanted)
        chosen = jnp.zeros((queries, t), bool).at[
            jnp.arange(queries)[:, None], best].set(True)
        return chosen & see

    out = _each(block, jnp.arange(0, t, queries),
                None if n is None else -(-n // queries))
    return out.reshape(t, t)


def _causal(t: int):
    import jax.numpy as jnp

    at = jnp.arange(t)
    return at[None, :] <= at[:, None]


def _mla(x, p, s, mm, n=None, chosen=None, dense: bool = False):
    """x (T, H) normed -> ((T, H), the choice attended over (T, T) bool):
    latent attention of one document over its choice of keys, a group of
    heads and a block of queries at a time. ``chosen``: an earlier layer's
    choice (None: the layer's own indexer makes one; ``dense``: every
    visible key, no indexer). With ``n`` the document is the first ``n``
    tokens of ``x``, and what the blocks behind them would give is not
    computed."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    nh, dn, dr, dv = s["nh"], s["dn"], s["dr"], s["dv"]
    kv_rank = s["kv_rank"]
    at = jnp.arange(t)

    def latents(xb):
        kv = mm(xb, p["kv_a"])
        latent = _rms_norm(kv[:, :kv_rank], p["kv_norm"], s["eps"])
        q = _rms_norm(mm(xb, p["q_a"]), p["q_norm"], s["eps"])
        return jnp.concatenate([latent, kv[:, kv_rank:], q], axis=-1)

    low = _by_tokens(latents, x, n)            # (T, kv_rank + dr + q_rank)
    latent, q_latent = low[:, :kv_rank], low[:, kv_rank + dr:]
    # one rotary key a token, for all heads
    k_rope = _turn(low[:, kv_rank:kv_rank + dr], at, s["theta"])   # (T, dr)
    if dense:
        chosen = _causal(t)
    elif chosen is None:
        chosen = _choose(x, q_latent, p["indexer"], s, mm, n)

    heads = math.gcd(nh, HEAD_GROUP)
    queries = math.gcd(t, QUERY_BLOCK)

    def group(ws):
        """``heads`` heads' (T, heads dv), their keys and values expanded
        from the latent."""
        q_b, kv_b = ws                 # (q_rank, heads (dn + dr)), (kv_rank, .)
        q = mm(q_latent, q_b).reshape(t, heads, dn + dr)
        kv = mm(latent, kv_b).reshape(t, heads, dn + dv).transpose(1, 0, 2)
        k_nope, v = kv[..., :dn], kv[..., dn:]                # (heads, T, .)
        q_nope = q[..., :dn].transpose(1, 0, 2)
        q_rope = _turn(q[..., dn:], at, s["theta"]).transpose(1, 0, 2)

        def block(start):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, queries,
                                                         axis=1)
            scores = (mm(cut(q_nope), k_nope.transpose(0, 2, 1))
                      + mm(cut(q_rope), k_rope.T)) * (dn + dr) ** -0.5
            see = jax.lax.dynamic_slice_in_dim(chosen, start, queries)
            probs = jax.nn.softmax(jnp.where(see, scores, -jnp.inf), axis=-1)
            # a query block behind the document has chosen nothing
            return mm(jnp.where(see, probs, 0.0), v)         # (heads, Q, dv)

        o = _each(block, jnp.arange(0, t, queries),          # (., heads, Q, dv)
                  None if n is None else -(-n // queries))
        return o.transpose(0, 2, 1, 3).reshape(t, heads * dv)

    by_group = lambda w, d: w.reshape(w.shape[0], nh // heads,
                                      heads * d).transpose(1, 0, 2)
    o = jax.lax.map(group, (by_group(p["q_b"], dn + dr),
                            by_group(p["kv_b"], dn + dv)))   # (groups, T, .)
    o = o.transpose(1, 0, 2).reshape(t, nh * dv)
    return _by_tokens(lambda ob: mm(ob, p["o"]), o, n), chosen


def _routing(x, p, s):
    """(T, outputs): the weight of every output of the router for every
    token, zero where it was not chosen: sigmoid scores, the choice over
    score + bias, the chosen scores renormalised to one and scaled."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"]))
    _, chosen = jax.lax.top_k(scores + p["bias"], s["k"])
    picked = jnp.any(chosen[:, :, None] == jnp.arange(s["experts"]), axis=1)
    kept = jnp.where(picked, scores, 0.0)
    return kept / jnp.sum(kept, axis=1, keepdims=True) * s["scale"]


def _moe(x, p, s, mm, first, shared):
    """x (T, H) -> (T, H): the part of the expert layer that the experts
    ``first``, ``first + 1``, ... of ``p`` (their matrices alone; the router
    whole) give, every one over every token, weighted by the router's
    choice; and ``shared`` (one or nought) times the shared expert's part,
    which no router gates."""
    import jax
    import jax.numpy as jnp

    weight = _routing(x, p, s)                              # (T, outputs)
    n = p["gate"].shape[0]
    mine = jax.lax.dynamic_slice_in_dim(weight, first, n, axis=1)

    def expert(y, xs):
        w_gate, w_up, w_down, w = xs
        out = _by_tokens(lambda xb: mm(
            jax.nn.silu(mm(xb, w_gate)) * mm(xb, w_up), w_down), x)
        return y + w[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (p["gate"], p["up"], p["down"], mine.T))
    every = {"gate": p["shared_gate"], "up": p["shared_up"],
             "down": p["shared_down"]}
    return y + shared * _ffn(x, every, mm)


def _layer(x, p, s, mm, kind: tuple, chosen=None, dense: bool = False):
    """One whole layer of one document, x (T, H) -> ((T, H), its choice):
    the definition (:func:`_embed` computes the same a part at a time).
    ``kind``: (``mlp_layer_types[l]``, ``indexer_types[l]``)."""
    mlp, indexer = kind
    y, chosen = _mla(_rms_norm(x, p["norm1"], s["eps"]), p["mixer"], s, mm,
                     None, chosen if indexer == "shared" else None, dense)
    x = x + y
    b = _rms_norm(x, p["norm2"], s["eps"])
    if mlp == "dense":
        return x + _ffn(b, p["ffn"], mm), chosen
    return x + _moe(b, p["moe"], s, mm, s["held"][0], 1.0), chosen


def _programs(s: dict, window: int, rows: int, mm, dense: bool):
    """The programs of a wave of ``rows`` tokens, each of (a part's weights,
    the wave's states, ...) and in place: latent attention over the first
    ``count`` documents of the wave one at a time (where they start, their
    lengths), each in a window of ``window`` tokens of which what lies
    behind the document is left as it was, once with the layer's own
    indexer, whose choices it packs a bit a pair into the wave's store, and
    once over the store's; a dense feed-forward, and a part of the expert
    layer (its first expert, one or nought for the shared expert's part)
    which takes the states' place, over the first ``blocks`` blocks of the
    wave."""
    import jax
    import jax.numpy as jnp

    eps = s["eps"]
    block = math.gcd(rows, TOKEN_BLOCK)

    def documents(own: bool):
        def attend(w, buf, store, starts, sizes, count):
            def one(i, carry):
                buf, store = carry
                x = jax.lax.dynamic_slice_in_dim(buf, starts[i], window)
                chosen = None if own or dense else jnp.unpackbits(
                    store[i], axis=1).astype(bool)
                y, chosen = _mla(_rms_norm(x, w["norm"], eps), w["mixer"], s,
                                 mm, sizes[i], chosen, dense)
                mine = jnp.arange(window)[:, None] < sizes[i]
                buf = jax.lax.dynamic_update_slice_in_dim(
                    buf, jnp.where(mine, x + y, x), starts[i], 0)
                if own and not dense:
                    store = store.at[i].set(jnp.packbits(chosen, axis=1))
                return buf, store

            return jax.lax.fori_loop(0, count, one, (buf, store))
        return jax.jit(attend, donate_argnums=(1, 2))

    def tokens(fn):
        def over(w, buf, blocks, *extra):
            def one(i, buf):
                x = jax.lax.dynamic_slice_in_dim(buf, i * block, block)
                return jax.lax.dynamic_update_slice_in_dim(
                    buf, fn(w, x, *extra), i * block, 0)

            return jax.lax.fori_loop(0, blocks, one, buf)
        return over

    feed = jax.jit(tokens(lambda w, x: x + _ffn(
        _rms_norm(x, w["norm"], eps), w["ffn"], mm)), donate_argnums=1)
    route = jax.jit(tokens(lambda w, x, first, shared: _moe(
        _rms_norm(x, w["norm"], eps), w["moe"], s, mm, first, shared)),
        donate_argnums=1)
    return {"full": documents(True), "shared": documents(False),
            "feed": feed, "route": route}


def _embed(params, token_ids, lengths, config: dict, mm,
           dense: bool = False) -> np.ndarray:
    """Layer by layer and within a layer part by part (a part's weights on
    the device at a time: the attention's, the dense feed-forward's, a few
    experts'), a wave of documents a call: the device takes the documents of
    a wave one at a time, each in a window as long as the longest of them
    (the model is causal, so what lies behind a document's last token does
    not reach it), and what is computed a token alone a block of the wave
    at a time. The waves' states and choices wait on the host; the next
    layer's weights are drawn while a layer is computed."""
    import jax
    import jax.numpy as jnp

    s = _sizes(config)
    (lo, hi), h = s["held"], s["h"]
    ids = np.asarray(token_ids, np.int32)
    lens = np.maximum(np.asarray(lengths, np.int64), 1)
    table = np.asarray(params["embed"], np.float32)
    window = min(ids.shape[1], -(-int(lens.max()) // TOKEN_BLOCK)
                 * TOKEN_BLOCK)
    aligned = int(sum(-(-int(n) // _ALIGN) * _ALIGN for n in lens))
    rows = -(-min(max(WAVE_TOKENS, 2 * window), aligned + window)
             // TOKEN_BLOCK) * TOKEN_BLOCK
    waves = _waves(lens, window, rows)
    most = max(len(wave["docs"]) for wave in waves)
    jitted = _programs(s, window, rows, mm, dense)
    states, stores = [], []
    for wave in waves:
        buf = np.zeros((rows, h), np.float32)
        for d, at in zip(wave["docs"], wave["starts"]):
            buf[at:at + lens[d]] = table[ids[d, :lens[d]]]
        states.append(buf)
        stores.append(np.zeros((most, window, window // 8), np.uint8))
        pad = lambda a: np.pad(np.asarray(a, np.int32),
                               (0, rows // _ALIGN - len(a)))
        wave["where"] = (pad(wave["starts"]),
                         pad([lens[d] for d in wave["docs"]]),
                         np.int32(len(wave["docs"])))
        wave["blocks"] = (np.int32(-(-wave["used"]
                                     // math.gcd(rows, TOKEN_BLOCK))),)
    # experts on the device at a time: the most that divides the held ones
    group = next(g for g in range(min(hi - lo, max(1, int(
        _EXPERT_BYTES // (12 * h * s["f"])))), 0, -1) if (hi - lo) % g == 0)

    def parts(number, layer):
        """A layer's parts in the order they are computed: (program, its
        weights, what it gives, the rest of its arguments)."""
        yield (s["indexers"][number], {"norm": layer["norm1"],
                                       "mixer": layer["mixer"]},
               "states", ())
        if s["mlp"][number] == "dense":
            yield ("feed", {"norm": layer["norm2"], "ffn": layer["ffn"]},
                   "states", ())
            return
        moe = layer["moe"]
        for at in range(0, hi - lo, group):
            yield ("route", {"norm": layer["norm2"], "moe": dict(moe, **{
                name: moe[name][at:at + group]
                for name in ("gate", "up", "down")})}, "added",
                (jnp.int32(lo + at), jnp.float32(at == 0)))

    def arguments(name, wave, buf, store, extra):
        if name in ("full", "shared"):
            return (buf, store, *wave["where"])
        return (buf, *wave["blocks"], *extra)

    def compiled(program, *args):
        """``program`` compiled for the shapes of ``args``."""
        with jax.default_matmul_precision("highest"):
            return program.lower(*jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a),
                                               np.result_type(a)),
                args)).compile()

    layers = params["layers"]
    with jax.default_matmul_precision("highest"), \
            ThreadPoolExecutor(4) as ahead:
        coming = ahead.submit(layers.__getitem__, 0)
        programs: dict = {}
        for number in range(len(layers)):
            layer = coming.result()
            coming = ahead.submit(layers.__getitem__, number + 1) \
                if number + 1 < len(layers) else None
            # a program compiles when the first layer that runs it comes, a
            # layer's side by side
            for name, part, _gives, extra in parts(number, layer):
                if name not in programs:
                    programs[name] = ahead.submit(
                        compiled, jitted[name], part, *arguments(
                            name, waves[0], states[0], stores[0], extra))
            added = None
            for name, part, gives, extra in parts(number, layer):
                on_device = jax.device_put(part)
                outs = [programs[name].result()(on_device, *arguments(
                    name, wave, jnp.asarray(buf), jnp.asarray(store), extra))
                    for wave, buf, store in zip(waves, states, stores)]
                del on_device
                if name in ("full", "shared"):
                    states = [np.asarray(buf) for buf, _store in outs]
                    stores = [np.asarray(store) for _buf, store in outs]
                elif gives == "states":
                    states = [np.asarray(buf) for buf in outs]
                else:
                    # the expert layer's parts all read the state before it
                    outs = [np.asarray(buf) for buf in outs]
                    added = outs if added is None else [
                        a + b for a, b in zip(added, outs)]
            if added is not None:
                states = [x + y for x, y in zip(states, added)]
            del layer, part, added
        last = np.zeros((len(ids), h), np.float32)
        for wave, buf in zip(waves, states):
            for d, at in zip(wave["docs"], wave["starts"]):
                last[d] = buf[at + lens[d] - 1]
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
    """:func:`embed` as something the configuration does not state would
    compute it, which ``correct`` has to refuse. ``"int8"``: every product
    of the latent attention (its projections and its two products), of the
    indexer (its projections and its scores), of the dense feed-forward and
    of the experts in int8, one scale a tensor (an expert's matrix is a
    tensor of its own, as checkpoints keep it; of what is computed a token
    alone, 1,024 tokens' activations are one): the nearest precision below
    the bfloat16 the configuration serves in; the router, its bias, the
    norms, the softmax and the choice stay float32. ``"dense"``: float32
    throughout, but no indexer: every query attends over every key it
    sees, what a program that ignored the learned choice would produce."""
    import jax.numpy as jnp

    if kind not in CONTROL_KINDS:
        raise ValueError(f"unknown control {kind!r}")
    if kind == "dense":
        return _embed(params, token_ids, lengths, config, jnp.matmul,
                      dense=True)
    return _embed(params, token_ids, lengths, config, _int8_matmul)
