"""Plain reference for the decoder of two latent attention sublayers, two
dense feed-forwards and a shortcut-connected expert layer with identity
experts, used as an embedder (``"model": "longcat_flash"``).

Independent of the code under test (it imports nothing of ``pathway_tpu``):
the layer equations of LongCat-Flash's language model as its ``config.json``
gives them (``meituan-longcat/LongCat-Flash-Omni``; the text path only), in
``jax.numpy`` float32 at ``highest`` matmul precision, with no kernel, no
packing and no batching: one document at a time, **attention by the full
mask of one block of 256 queries at a time** against every key of the
document, eight heads' keys and values expanded at a time (the unabsorbed
form), **every held expert applied to every token** and weighted by a mask
that is zero where the router did not choose it.

The layer (all norms RMSNorm with a plain weight, ``x / rms(x) * w``; no
biases), ``H`` the hidden size::

    h = x + MLA_0(norm_in0(x))
    a = norm_post0(h)
    s = MoE(a)                                # the shortcut: joins at the end
    h = h + FFN_0(a)                          # W_down(silu(W_gate a) * (W_up a))
    h = h + MLA_1(norm_in1(h))
    y = h + FFN_1(norm_post1(h)) + s

    MLA(u):  q = W_qb norm_q(W_qa u) -> (heads, nope + rope), times
             (H / q_lora_rank) ** 0.5
             c, k_r = split(W_kva u, [kv_lora_rank, rope]); c = norm_kv(c)
             times (H / kv_lora_rank) ** 0.5
             k_nope, v = split(W_kvb c -> (heads, nope + v_head_dim))
             q_rope and k_r rotated (neighbouring pairs, theta), k_r one
             vector for all heads
             score = (q_nope . k_nope + q_rope . k_r) (nope + rope) ** -0.5,
             causal, float32 softmax; out = W_o concat_heads(softmax v)
    MoE(a):  p = softmax(a W_r) over the experts with weights and the
             identity experts together; the moe_topk largest of p + bias are
             chosen; their weights are p there, not renormalised, times
             routed_scaling_factor; an expert with weights is SwiGLU, an
             identity expert returns a

and the embedding is the final norm's state of the last token,
L2-normalised.

**The share.** The configuration holds a range of the experts with weights
(``experts_held`` of ``published.n_routed_experts``): the router keeps every
output, and only held experts and the identity experts add. What the absent
experts would have added is left out, here as in the program.

**Departures and readings** (the configuration's ``assumed`` lists them
too): the router has no bias of its own; the correction bias is seeded
(deviation 0.01); the chosen weights are not renormalised; rotary positions
turn neighbouring pairs of features (the family's convention: a query and a
key turned alike score alike under any pairing, the half-split one
included); ``lm_head`` takes no part in an embedding and is absent; token
ids are the program's WordPiece ids.

**Memory and time.** At the published cut a layer is 1.24 billion float32
numbers (5 GB) and the four of them with the embedding 20.3 GB:
:func:`weights` makes the embedding at once and **a layer when it is asked
for** (``params["layers"][i]``, anew at every asking); the check holds the
layer it computes and the next, which a thread draws meanwhile. On the
device the reference runs beside the program it checks, which leaves it
about 4 GB: a layer's weights go there a part at a time (a sublayer, a
feed-forward, eight experts), and what is computed a token alone runs 1,024
tokens at a time. **The program under test keeps ingesting its backlog on
the same chip while the check runs, and every call of the reference queues
behind its dispatches** (up to two of 0.43 s): with a call a document and
part the check took twelve minutes for one of device work, with two
documents a call six and a half, and a run has six in all (my chip runs,
PR 35). So the documents' states lie one behind the other in a buffer of
eight serving widths of tokens (a *wave*: 1.6 GB at the published shape,
which the cell's sixteen sampled sections fill to five sixths), and **a
part is one call a wave**: the device walks the documents of a wave itself,
each in a window of a quarter, a half, three quarters or the whole of the
serving width (the model is causal, so what lies behind a document's last
token in its window, the next documents, never reaches it, and is left as
it was).
"""

from __future__ import annotations

import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# bf16 vs float32 agreement, as the cosine between the two unit embeddings
# of one text, over the 16 documents a run samples. Each limit stands
# between two readings of 1 - cos on the chip at the published widths (my
# chip runs, PR 35: the runs' own lines, and the program beside the int8
# ``control`` on three more seeds, one process a seed, 16 documents each;
# PERF.md section 2 has every reading):
#   the mean over the texts: program 0.86e-3 to 1.07e-3 (steady from seed
#     to seed: eight attention sublayers, eight dense feed-forwards and four
#     expert layers of bfloat16 products deep), the int8 control 0.137 to
#     0.175 (0.155 and 0.175 with a scale a block of 64 queries and all
#     heads' expansion, 0.137 with one a block of 256 and eight heads', as
#     it is now), a hundred and thirty times apart: limit 5e-3, 4.7 times
#     the program's largest and 27 times under the control's smallest. This
#     is the number that holds the control;
#   the worst text: program 1.1e-3 to 3.3e-3, the control 0.222 to 0.232
#     (67 times the program's largest: its upper reading here too). Limit
#     2e-2: six times the program's largest, a tenth of the control's
#     smallest; a text gone wrong (a document attending its neighbour in a
#     packed row, a wrong pooled token) reads as two different documents
#     do: 0.95 and more at the published widths (CPU check over one layer,
#     twelve documents, PR 35; at toy widths documents lie 0.01 apart).
MIN_COS = 0.98
MIN_MEAN_COS = 0.995

#: the lower precisions :func:`control` can compute in; the first is *the*
#: control, which ``correct`` has to refuse
CONTROL_KINDS = ("int8",)

_THREADS = min(8, os.cpu_count() or 1)
#: numbers a generator of its own draws: a fixed cut, so that the values
#: depend on the seed alone and not on the threads that drew them
_BLOCK = 1 << 24
#: queries whose scores are held at once, and heads whose keys and values
#: are expanded at once (at 8,192 keys a block's scores are 67 MB in float32,
#: and the softmax holds three such)
QUERY_BLOCK = 256
HEAD_GROUP = 8
#: tokens at a time through what is computed a token alone
TOKEN_BLOCK = 1024
#: serving widths of tokens in a wave, the buffer of documents' states that
#: one call to the device computes (1.6 GB at the published shape)
WAVE_WIDTHS = 8
#: a document's state starts at a multiple of this many tokens of its wave
_ALIGN = 8
#: float32 bytes of experts on the device at a time
_EXPERT_BYTES = 1.3e9
#: a layer's tensors are numbered from ``1 + layer * _TENSORS_A_LAYER``
_TENSORS_A_LAYER = 32


def _sizes(config: dict) -> dict:
    c = config
    lo, hi = c.get("experts_held") or (0, c["n_routed_experts"])
    return dict(
        h=c["hidden_size"], nh=c["num_attention_heads"],
        q_rank=c["q_lora_rank"], kv_rank=c["kv_lora_rank"],
        dn=c["qk_nope_head_dim"], dr=c["qk_rope_head_dim"],
        dv=c["v_head_dim"], ffn=c["ffn_hidden_size"],
        f=c["expert_ffn_hidden_size"],
        experts=c.get("published", c)["n_routed_experts"],
        zero=c["zero_expert_num"], k=c["moe_topk"],
        scale=float(c["routed_scaling_factor"]), held=(lo, hi),
        q_scale=(c["hidden_size"] / c["q_lora_rank"]) ** 0.5
        if c["mla_scale_q_lora"] else 1.0,
        kv_scale=(c["hidden_size"] / c["kv_lora_rank"]) ** 0.5
        if c["mla_scale_kv_lora"] else 1.0,
        theta=float(c["rope_theta"]), eps=c["rms_norm_eps"])


def _draw(jobs: list) -> None:
    """Fill every (view, generator key, deviation) of ``jobs``, threads
    side by side."""

    def draw(job):
        view, key, deviation = job
        np.random.default_rng(key).standard_normal(
            view.shape, dtype=np.float32, out=view)
        view *= np.float32(deviation)

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(draw, jobs))


class _Layers:
    """The layers' float32 weights, each made from the seed when it is
    asked for and not kept: ``layers[i]`` is a new tree at every asking
    (5 GB at the published cut)."""

    def __init__(self, config: dict, seed: int):
        self.config, self.seed = config, seed

    def __len__(self) -> int:
        return self.config["num_layers"]

    def __iter__(self):
        return (self[i] for i in range(len(self)))

    def __getitem__(self, layer: int) -> dict:
        if not 0 <= layer < len(self):
            raise IndexError(layer)
        s, seed = _sizes(self.config), self.seed
        h, nh = s["h"], s["nh"]
        jobs, tensor = [], [1 + layer * _TENSORS_A_LAYER]

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

        def latent():
            return {"q_a": dense(h, s["q_rank"]), "q_norm": ones(s["q_rank"]),
                    "q_b": dense(s["q_rank"], nh * (s["dn"] + s["dr"])),
                    "kv_a": dense(h, s["kv_rank"] + s["dr"]),
                    "kv_norm": ones(s["kv_rank"]),
                    "kv_b": dense(s["kv_rank"], nh * (s["dn"] + s["dv"])),
                    "o": dense(nh * s["dv"], h)}

        def ffn():
            return {"gate": dense(h, s["ffn"]), "up": dense(h, s["ffn"]),
                    "down": dense(s["ffn"], h)}

        lo, hi = s["held"]
        outputs = s["experts"] + s["zero"]
        tree = {"norm_in": [ones(h), ones(h)],
                "norm_post": [ones(h), ones(h)],
                "mixer": [latent(), latent()], "ffn": [ffn(), ffn()],
                "moe": {"router": dense(h, outputs),
                        "bias": dense(outputs, deviation=0.01),
                        "gate": dense(hi - lo, h, s["f"], first=lo),
                        "up": dense(hi - lo, h, s["f"], first=lo),
                        "down": dense(hi - lo, s["f"], h, first=lo)}}
        _draw(jobs)
        return tree


def weights(config: dict, seed: int) -> dict:
    """The float32 weights of the configuration's model from ``seed``, in
    the program's tree: every matrix and table normal of deviation 0.02,
    the router's correction bias 0.01, every norm's weight one. Each block
    of 2**24 numbers has a generator of its own, seeded by (seed, tensor,
    expert, block), so threads draw them side by side and the values depend
    on the seed alone. ``"layers"`` makes a layer when it is indexed
    (:class:`_Layers`); ``dict(w, layers=list(w["layers"]))`` is the whole
    tree, for a model small enough to hold."""
    table = np.empty((config["vocab_size"], config["hidden_size"]),
                     np.float32)
    flat = table.reshape(-1)
    _draw([(flat[i:i + _BLOCK], (seed, 0, 0, i // _BLOCK), 0.02)
           for i in range(0, flat.size, _BLOCK)])
    return {"embed": table, "layers": _Layers(config, seed),
            "final_norm": np.ones(config["hidden_size"], np.float32)}


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


def _each(fn, xs, live=None):
    """``fn`` of every one of ``xs``, stacked; with ``live``, of the first
    ``live`` alone, and zeros for the rest (a document shorter than the
    window it is computed in leaves the blocks behind it out)."""
    import jax
    import jax.numpy as jnp

    if live is None:
        return jax.lax.map(fn, xs)
    one = jax.eval_shape(fn, xs[0])
    return jax.lax.fori_loop(
        0, live, lambda i, out: out.at[i].set(fn(xs[i])),
        jnp.zeros((len(xs),) + one.shape, one.dtype))


def _by_tokens(fn, x, n=None):
    """``fn`` over ``x`` (T, ...) :data:`TOKEN_BLOCK` tokens at a time, for
    what is computed a token alone; with ``n``, over the blocks that hold
    the first ``n`` tokens alone."""
    t = x.shape[0]
    if t <= TOKEN_BLOCK or t % TOKEN_BLOCK:
        return fn(x)
    out = _each(fn, x.reshape((t // TOKEN_BLOCK, TOKEN_BLOCK) + x.shape[1:]),
                None if n is None else -(-n // TOKEN_BLOCK))
    return out.reshape((t,) + out.shape[2:])


def _turn(x, at, theta):
    """Rotate the neighbouring pairs of the last axis of ``x`` (T, ..., d)
    by the positions ``at`` (T,)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = at.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * freq
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle),
                        b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)
    return turned.reshape(x.shape)


def _mla(x, p, s, mm, n=None):
    """x (T, H) normed -> (T, H): latent attention of one document, a group
    of heads and a block of queries at a time against every key under the
    causal mask. With ``n`` the document is the first ``n`` tokens of ``x``,
    and what the blocks behind them would give is not computed."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    nh, dn, dr, dv = s["nh"], s["dn"], s["dr"], s["dv"]
    q_rank, kv_rank = s["q_rank"], s["kv_rank"]
    at = jnp.arange(t)

    def latents(xb):
        kv = mm(xb, p["kv_a"])
        latent = _rms_norm(kv[:, :kv_rank], p["kv_norm"], s["eps"]) \
            * s["kv_scale"]
        q = _rms_norm(mm(xb, p["q_a"]), p["q_norm"], s["eps"])
        return jnp.concatenate([latent, kv[:, kv_rank:], q], axis=-1)

    low = _by_tokens(latents, x, n)            # (T, kv_rank + dr + q_rank)
    latent, q_latent = low[:, :kv_rank], low[:, kv_rank + dr:]
    # one rotary key a token, for all heads
    k_rope = _turn(low[:, kv_rank:kv_rank + dr], at, s["theta"])   # (T, dr)

    heads = math.gcd(nh, HEAD_GROUP)
    queries = math.gcd(t, QUERY_BLOCK)

    def group(ws):
        """``heads`` heads' (T, heads dv), their keys and values expanded
        from the latent."""
        q_b, kv_b = ws                 # (q_rank, heads (dn + dr)), (kv_rank, .)
        q = mm(q_latent, q_b).reshape(t, heads, dn + dr) * s["q_scale"]
        kv = mm(latent, kv_b).reshape(t, heads, dn + dv).transpose(1, 0, 2)
        k_nope, v = kv[..., :dn], kv[..., dn:]                # (heads, T, .)
        q_nope = q[..., :dn].transpose(1, 0, 2)
        q_rope = _turn(q[..., dn:], at, s["theta"]).transpose(1, 0, 2)

        def block(start):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, queries,
                                                         axis=1)
            here = start + jnp.arange(queries)
            scores = (mm(cut(q_nope), k_nope.transpose(0, 2, 1))
                      + mm(cut(q_rope), k_rope.T)) * (dn + dr) ** -0.5
            see = at[None, :] <= here[:, None]               # (Q, T)
            probs = jax.nn.softmax(jnp.where(see, scores, -jnp.inf), axis=-1)
            return mm(probs, v)                              # (heads, Q, dv)

        o = _each(block, jnp.arange(0, t, queries),          # (., heads, Q, dv)
                  None if n is None else -(-n // queries))
        return o.transpose(0, 2, 1, 3).reshape(t, heads * dv)

    by_group = lambda w, d: w.reshape(w.shape[0], nh // heads,
                                      heads * d).transpose(1, 0, 2)
    o = jax.lax.map(group, (by_group(p["q_b"], dn + dr),
                            by_group(p["kv_b"], dn + dv)))   # (groups, T, .)
    o = o.transpose(1, 0, 2).reshape(t, nh * dv)
    return _by_tokens(lambda ob: mm(ob, p["o"]), o, n)


def _ffn(x, p, mm):
    import jax

    return _by_tokens(lambda xb: mm(
        jax.nn.silu(mm(xb, p["gate"])) * mm(xb, p["up"]), p["down"]), x)


def _routing(x, p, s):
    """(T, outputs): the weight of every output of the router for every
    token, zero where it was not chosen."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(jnp.matmul(x, p["router"]), axis=-1)
    _, chosen = jax.lax.top_k(probs + p["bias"], s["k"])
    outputs = s["experts"] + s["zero"]
    picked = jnp.any(chosen[:, :, None] == jnp.arange(outputs), axis=1)
    return jnp.where(picked, probs, 0.0) * s["scale"]


def _moe(x, p, s, mm, first, identity):
    """x (T, H) -> (T, H): the part of the expert layer that the experts
    ``first``, ``first + 1``, ... of ``p`` (their matrices alone; the router
    whole) give, every one over every token, weighted by the router's
    choice; and ``identity`` (one or nought) times the identity experts'
    part, the sum of their weights times ``x``."""
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
    return y + identity * jnp.sum(weight[:, s["experts"]:], axis=1,
                                  keepdims=True) * x


def _layer(x, p, s, mm):
    """One whole layer of one document, x (T, H) -> (T, H): the definition
    (:func:`_embed` computes the same a part at a time)."""
    h = x + _mla(_rms_norm(x, p["norm_in"][0], s["eps"]), p["mixer"][0], s,
                 mm)
    a = _rms_norm(h, p["norm_post"][0], s["eps"])
    shortcut = _moe(a, p["moe"], s, mm, s["held"][0], 1.0)
    h = h + _ffn(a, p["ffn"][0], mm)
    h = h + _mla(_rms_norm(h, p["norm_in"][1], s["eps"]), p["mixer"][1], s,
                 mm)
    return h + _ffn(_rms_norm(h, p["norm_post"][1], s["eps"]),
                    p["ffn"][1], mm) + shortcut


def _waves(lens, window: int, rows: int) -> list[dict]:
    """The documents laid one behind the other, shortest first, into waves
    of ``rows`` tokens: a wave's documents, where each starts, and the
    tokens used. A document is computed in a window of the serving width,
    which reaches over the documents behind it and has to end inside the
    wave."""
    waves: list[dict] = []
    for d in sorted(range(len(lens)), key=lambda i: lens[i]):
        wave = waves[-1] if waves else None
        if wave is None or wave["used"] + window > rows:
            wave = {"docs": [], "starts": [], "used": 0}
            waves.append(wave)
        wave["docs"].append(d)
        wave["starts"].append(wave["used"])
        wave["used"] += -(-int(lens[d]) // _ALIGN) * _ALIGN
    return waves


def _programs(s: dict, window: int, rows: int, mm):
    """The three programs of a wave of ``rows`` tokens, each of (a part's
    weights, the wave's states, ...) and in place: a latent attention
    sublayer over the first ``count`` documents of the wave one at a time
    (where they start, their lengths), each in a window of ``window``
    tokens of which what lies behind the document is left as it was; a
    dense feed-forward, and a part of the expert layer (its first expert,
    one or nought for the identity experts' part) which takes the states'
    place, over the first ``blocks`` blocks of the wave."""
    import jax
    import jax.numpy as jnp

    eps = s["eps"]
    block = math.gcd(rows, TOKEN_BLOCK)

    def documents(w, buf, starts, sizes, count):
        def one(i, buf):
            x = jax.lax.dynamic_slice_in_dim(buf, starts[i], window)
            y = x + _mla(_rms_norm(x, w["norm"], eps), w["mixer"], s, mm,
                         sizes[i])
            mine = jnp.arange(window)[:, None] < sizes[i]
            return jax.lax.dynamic_update_slice_in_dim(
                buf, jnp.where(mine, y, x), starts[i], 0)

        return jax.lax.fori_loop(0, count, one, buf)

    def tokens(fn):
        def over(w, buf, blocks, *extra):
            def one(i, buf):
                x = jax.lax.dynamic_slice_in_dim(buf, i * block, block)
                return jax.lax.dynamic_update_slice_in_dim(
                    buf, fn(w, x, *extra), i * block, 0)

            return jax.lax.fori_loop(0, blocks, one, buf)
        return over

    attend = jax.jit(documents, donate_argnums=1)
    feed = jax.jit(tokens(lambda w, x: x + _ffn(
        _rms_norm(x, w["norm"], eps), w["ffn"], mm)), donate_argnums=1)
    route = jax.jit(tokens(lambda w, x, first, identity: _moe(
        _rms_norm(x, w["norm"], eps), w["moe"], s, mm, first, identity)),
        donate_argnums=1)
    return attend, feed, route


def _embed(params, token_ids, lengths, config: dict, mm) -> np.ndarray:
    """Layer by layer and within a layer part by part (a part's weights on
    the device at a time: a sublayer's, a feed-forward's, a few experts'),
    a wave of documents a call: the device takes the documents of a wave
    one at a time, each in a window of the serving width (the model is
    causal, so what lies behind a document's last token does not reach it),
    and what is computed a token alone a block of the wave at a time. The
    waves' states wait on the host; the next layer's weights are drawn
    while a layer is computed."""
    import jax
    import jax.numpy as jnp

    s = _sizes(config)
    (lo, hi), h = s["held"], s["h"]
    ids = np.asarray(token_ids, np.int32)
    lens = np.maximum(np.asarray(lengths, np.int64), 1)
    table = np.asarray(params["embed"], np.float32)
    window = ids.shape[1]
    rows = WAVE_WIDTHS * window
    waves = _waves(lens, window, rows)
    jitted = dict(zip(("attend", "feed", "route"),
                      _programs(s, window, rows, mm)))
    states = []
    for wave in waves:
        buf = np.zeros((rows, h), np.float32)
        for d, at in zip(wave["docs"], wave["starts"]):
            buf[at:at + lens[d]] = table[ids[d, :lens[d]]]
        states.append(buf)
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

    def parts(layer):
        """A layer's parts in the order they are computed: (program, its
        weights, what it gives, what of the wave it is told, the rest)."""
        moe = layer["moe"]
        for i in range(2):
            yield ("attend", {"norm": layer["norm_in"][i],
                              "mixer": layer["mixer"][i]},
                   "states", "where", ())
            if i == 0:
                for at in range(0, hi - lo, group):
                    yield ("route", {"norm": layer["norm_post"][0],
                                     "moe": dict(moe, **{
                                         name: moe[name][at:at + group]
                                         for name in ("gate", "up", "down")})},
                           "shortcut", "blocks",
                           (jnp.int32(lo + at), jnp.float32(at == 0)))
            yield ("feed", {"norm": layer["norm_post"][i],
                            "ffn": layer["ffn"][i]}, "states", "blocks", ())

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
        programs = None
        for number in range(len(layers)):
            layer = coming.result()
            coming = ahead.submit(layers.__getitem__, number + 1) \
                if number + 1 < len(layers) else None
            if programs is None:
                # the three programs compile side by side (half a minute
                # one behind the other on the chip's machine, cold)
                programs = {}
                for name, part, _, key, extra in parts(layer):
                    if name not in programs:
                        programs[name] = ahead.submit(
                            compiled, jitted[name], part, states[0],
                            *waves[0][key], *extra)
            shortcuts = None
            for name, part, gives, key, extra in parts(layer):
                # the shortcut: the expert layer reads the first sublayer's
                # state, and what it gives waits for the layer's end
                on_device = jax.device_put(part)
                outs = [np.asarray(programs[name].result()(
                    on_device, jnp.asarray(buf), *wave[key], *extra))
                    for wave, buf in zip(waves, states)]
                del on_device
                if gives == "states":
                    states = outs
                else:
                    shortcuts = outs if shortcuts is None else [
                        a + b for a, b in zip(shortcuts, outs)]
            states = [x + shortcut for x, shortcut in zip(states, shortcuts)]
            del layer, part, shortcuts
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
    """:func:`embed` with every product of the latent attention sublayers
    (their projections and their two products), of the dense feed-forwards
    and of the experts in int8, one scale a tensor (an expert's matrix is a
    tensor of its own, as checkpoints keep it; of what is computed a token
    alone, 1,024 tokens' activations are one): the nearest precision below
    the bfloat16 the configuration serves in. The router, its bias, the
    norms and the softmax stay float32, as the configuration's ``serving``
    keeps them: what a later PR that served in int8 would produce at best,
    and ``correct`` has to refuse it."""
    if kind not in CONTROL_KINDS:
        raise ValueError(f"unknown control {kind!r}")
    return _embed(params, token_ids, lengths, config, _int8_matmul)
