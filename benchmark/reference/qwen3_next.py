"""Plain reference for the hybrid DeltaNet/attention decoder with routed
experts, used as an embedder (``"model": "qwen3_next"``).

Independent of the code under test (it imports nothing of ``pathway_tpu``):
the layer equations of Qwen3-Next as published (``config.json`` and the
modelling code of ``Qwen/Qwen3-Next-80B-A3B-Instruct``), in ``jax.numpy``
float32 at ``highest`` matmul precision, with no kernel, no chunking, no
packing and no batching: one document at a time, the DeltaNet's state
advanced **token by token** in a ``lax.scan``, **every held expert applied
to every token** and weighted by a mask that is zero where the router did
not choose it. One layer's float32 weights (3.4 GB at the published widths)
are on the device at a time, so that the reference fits beside the program
it checks.

The layer (all norms RMSNorm with ``1 + w``; no biases)::

    x = x + mixer(norm1(x));  x = x + moe(norm2(x))

- layer ``i`` is gated attention where ``(i + 1) % full_attention_interval
  == 0``, else Gated DeltaNet;
- Gated DeltaNet: ``in_proj_qkvz`` -> q, k (key heads), v, z (value
  heads); ``in_proj_ba`` -> b, a; ``concat(q, k, v)`` through a causal
  depthwise convolution of ``linear_conv_kernel_dim`` taps, then SiLU;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; q, k
  L2-normalised over the head, q scaled by ``dk ** -0.5``; a key head
  serves ``nv / nk`` value heads; per value head ``S <- exp(g) S; S <- S +
  k (beta (v - S^T k))^T; o = S^T q``; ``rmsnorm(o) * w * silu(z)`` (this
  norm's weight is plain), ``out_proj``;
- gated attention: ``q_proj`` gives a head its query and its gate;
  RMSNorm over the head on q and k; rotary on the first
  ``partial_rotary_factor`` of a head; causal softmax over grouped heads;
  ``o * sigmoid(gate)``, ``o_proj``;
- experts: ``p = softmax(x W_r)`` over all ``num_experts_routed``; the
  ``num_experts_per_tok`` largest, renormalised to sum 1; ``E(x) = W_down
  (silu(W_gate x) * (W_up x))``; ``y = sum_e p_e E_e(x) + sigmoid(x w_sg)
  E_shared(x)``;
- the embedding: final norm, the state of the last token, L2-normalised.

**The share.** The configuration holds a range of the routed experts
(``experts_held``: this chip's under expert parallelism). The router keeps
all its outputs and its k a token, the weights are renormalised over all k,
and only held experts add to ``y``: what the absent experts would have
added is left out here as in the program, and that partial result goes on
to the next layer.

**Departures**, the configuration's (its ``assumed``): ``lm_head`` and the
multi-token-prediction layer take no part in an embedding and are absent;
the columns of ``in_proj_qkvz`` lie ``[q | k | v | z]`` and those of
``in_proj_ba`` ``[b | a]`` (the published checkpoint interleaves them by key
head; with seeded weights the order is a labelling); token ids are the
program's WordPiece ids inside the held slice of the vocabulary.

**Host memory.** :func:`weights` makes 3.52 billion float32 numbers at the
published cut: 14.1 GB, made once at build and again at the check (never two
copies at once: ``build`` keeps a bfloat16 copy on the device and drops the
float32 one).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# bf16 vs float32 agreement, as the cosine between the two unit embeddings
# of one text, over the 64 documents a run samples. What sets it here is not
# the rounding of a sum but **the router**: a token's ten experts are the
# ten largest of 512 probabilities that seeded weights leave nearly level,
# and a bf16 rounding upstream of the router (the router itself is float32
# in program and reference alike) swaps the tenth for the eleventh: a
# discrete change. On the chip 9 % of the sampled tokens chose another set
# than the reference's in layer 0, 26, 47 and 74 % in layers 1-3, where the
# two trajectories have drifted apart (4,426 tokens of 16 documents, seed
# 2800003); with seeded weights an expert layer's output is larger than the
# residual it is added to, so a swap moves a token's state by a fifth. The
# same program with float32 products over the reference's float32 weights
# agrees to 1e-7 (CPU, 512 routed, 256 held, published widths), and bf16 on
# the CPU reads what the chip reads: it is the precision, not the kernels.
# Each limit stands between two readings of 1 - cos on the chip at the
# published widths (my chip runs, PR 28: ``tools/control.py``'s ``readings``
# on 11 seeds, 64 documents each, and the runs' own lines; PERF.md section
# 2):
#   the mean over the texts: program 0.0075-0.0200, the int8 ``control``
#     0.147-0.203, 7.3 times apart at the nearest: limit 0.06, three times
#     the program's largest and 2.4 times under the control's smallest. This
#     is the number that holds the control;
#   the worst text: program 0.072-0.208, a widest gap that swings with
#     which token a swap hits; the control reads 0.33-0.57, 1.6 times away
#     at the nearest, and is not this number's upper reading. What the limit
#     is there for is one text gone wrong (a document attending its
#     neighbour in a packed row, a state not reset, a wrong pooled token):
#     two different documents' embeddings are 0.015 alike in the mean and
#     0.117 at most (64 documents, seed 2800202), so a wrong text reads
#     0.88 or more. Limit 0.45: 2.2 times the program's largest, half a
#     wrong text's least.
MIN_COS = 0.55
MIN_MEAN_COS = 0.94

#: the lower precisions :func:`control` can compute in; the first is *the*
#: control, which ``correct`` has to refuse
CONTROL_KINDS = ("int8",)

_THREADS = min(8, os.cpu_count() or 1)
#: numbers a generator of its own draws: a fixed cut, so that the values
#: depend on the seed alone and not on the threads that drew them
_BLOCK = 1 << 24


def _sizes(config: dict) -> dict:
    c = config
    lo, hi = c["experts_held"]
    nk, nv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    return dict(
        h=c["hidden_size"], held=hi - lo, lo=lo,
        routed=c["num_experts_routed"], k=c["num_experts_per_tok"],
        f=c["moe_intermediate_size"],
        fs=c["shared_expert_intermediate_size"],
        nh=c["num_attention_heads"], nkv=c["num_key_value_heads"],
        hd=c["head_dim"], rot=int(c["head_dim"] * c["partial_rotary_factor"]),
        theta=float(c["rope_theta"]), nk=nk, nv=nv,
        dk=c["linear_key_head_dim"], dv=c["linear_value_head_dim"],
        kd=nk * c["linear_key_head_dim"], vd=nv * c["linear_value_head_dim"],
        taps=c["linear_conv_kernel_dim"], eps=c["rms_norm_eps"],
        interval=c["full_attention_interval"])


def weights(config: dict, seed: int) -> dict:
    """The float32 weights of the configuration's model from ``seed``, in
    the program's tree: every matrix and table normal of deviation 0.02
    (the family's ``initializer_range``), zero-centred norm weights zero,
    the DeltaNet's output norm one, ``dt_bias`` one and ``A_log`` the log
    of a uniform draw from (0, 16] (the published code's initialiser).
    Each block of 2**24 numbers has a generator of its own, seeded by
    (seed, tensor, block), so threads draw them side by side and the
    values depend on the seed alone. A new tree at every call."""
    s = _sizes(config)
    h, held, f, fs = s["h"], s["held"], s["f"], s["fs"]
    jobs, counter = [], [0]

    def dense(*shape):
        out = np.empty(shape, np.float32)
        flat, tensor = out.reshape(-1), counter[0]
        counter[0] += 1
        jobs.extend((flat[i:i + _BLOCK], (seed, tensor, i // _BLOCK))
                    for i in range(0, flat.size, _BLOCK))
        return out

    def zeros(n):
        return np.zeros(n, np.float32)

    def a_log(n):
        rng = np.random.default_rng((seed, counter[0]))
        counter[0] += 1
        return np.log(16.0 * (1.0 - rng.random(n))).astype(np.float32)

    layers = []
    for i in range(config["num_hidden_layers"]):
        if (i + 1) % s["interval"] == 0:
            mixer = {"q_proj": dense(h, s["nh"] * 2 * s["hd"]),
                     "k_proj": dense(h, s["nkv"] * s["hd"]),
                     "v_proj": dense(h, s["nkv"] * s["hd"]),
                     "q_norm": zeros(s["hd"]), "k_norm": zeros(s["hd"]),
                     "o_proj": dense(s["nh"] * s["hd"], h)}
        else:
            mixer = {"in_proj_qkvz": dense(h, 2 * s["kd"] + 2 * s["vd"]),
                     "in_proj_ba": dense(h, 2 * s["nv"]),
                     "conv": dense(s["taps"], 2 * s["kd"] + s["vd"]),
                     "A_log": a_log(s["nv"]),
                     "dt_bias": np.ones(s["nv"], np.float32),
                     "norm": np.ones(s["dv"], np.float32),
                     "out_proj": dense(s["vd"], h)}
        layers.append({
            "norm1": zeros(h), "norm2": zeros(h), "mixer": mixer,
            "moe": {"router": dense(h, s["routed"]),
                    "gate": dense(held, h, f), "up": dense(held, h, f),
                    "down": dense(held, f, h),
                    "shared_gate": dense(h, fs), "shared_up": dense(h, fs),
                    "shared_down": dense(fs, h),
                    "shared_router": dense(h, 1)}})
    params = {"embed": dense(config["vocab_size"], h), "layers": layers,
              "final_norm": zeros(h)}

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


def _rms_norm(x, w, eps, zero_centred=True):
    import jax.numpy as jnp

    x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w if zero_centred else w)


def _deltanet(x, p, s, mm):
    """x (T, H) -> (T, H): the Gated DeltaNet mixer of one document."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    nk, nv, dk, dv, kd, vd = (s[n] for n in ("nk", "nv", "dk", "dv", "kd",
                                             "vd"))
    qkvz, ba = mm(x, p["in_proj_qkvz"]), mm(x, p["in_proj_ba"])
    qkv, z = qkvz[:, :2 * kd + vd], qkvz[:, 2 * kd + vd:]
    taps = s["taps"]
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[i:i + t] * p["conv"][i]
                          for i in range(taps)))
    q = qkv[:, :kd].reshape(t, nk, dk)
    k = qkv[:, kd:2 * kd].reshape(t, nk, dk)
    v = qkv[:, 2 * kd:].reshape(t, nv, dv)
    beta = jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, nv:] + p["dt_bias"])
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q, k = unit(q) * dk ** -0.5, unit(k)
    q, k = (jnp.repeat(a, nv // nk, axis=1) for a in (q, k))

    def token(state, xs):                      # state (nv, dk, dv)
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[:, None, None]
        read = jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + jnp.einsum("hk,hv->hkv", k_t,
                                   (v_t - read) * beta_t[:, None])
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    _state, o = jax.lax.scan(token, jnp.zeros((nv, dk, dv), jnp.float32),
                             (q, k, v, g, beta))
    o = _rms_norm(o, p["norm"], s["eps"], zero_centred=False) \
        * jax.nn.silu(z.reshape(t, nv, dv))
    return mm(o.reshape(t, vd), p["out_proj"])


def _attention(x, p, s, mm):
    """x (T, H) -> (T, H): gated causal attention of one document."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    nh, nkv, hd, rot = s["nh"], s["nkv"], s["hd"], s["rot"]
    qg = mm(x, p["q_proj"]).reshape(t, nh, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = mm(x, p["k_proj"]).reshape(t, nkv, hd)
    v = mm(x, p["v_proj"]).reshape(t, nkv, hd)
    half = rot // 2
    freq = s["theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)

    def rotary(a):
        a1, a2, rest = a[..., :half], a[..., half:rot], a[..., rot:]
        return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin,
                                rest], axis=-1)

    q = rotary(_rms_norm(q, p["q_norm"], s["eps"])).transpose(1, 0, 2)
    k = rotary(_rms_norm(k, p["k_norm"], s["eps"])).transpose(1, 0, 2)
    k, v = (jnp.repeat(a, nh // nkv, axis=0)
            for a in (k, v.transpose(1, 0, 2)))            # (nh, T, hd)
    scores = mm(q, k.transpose(0, 2, 1)) * hd ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = mm(probs, v).transpose(1, 0, 2) * jax.nn.sigmoid(gate)
    return mm(o.reshape(t, nh * hd), p["o_proj"])


def _moe(x, p, s, mm):
    """x (T, H) -> (T, H): every held expert over every token, weighted by
    the router's choice (zero where it chose another), plus the shared
    expert."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(jnp.matmul(x, p["router"]), axis=-1)
    top, chosen = jax.lax.top_k(probs, s["k"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    held = s["lo"] + jnp.arange(s["held"])
    weight = jnp.sum(jnp.where(chosen[:, :, None] == held[None, None, :],
                               top[:, :, None], 0.0), axis=1)    # (T, held)

    def expert(y, xs):
        w_gate, w_up, w_down, w = xs
        out = mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)
        return y + w[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (p["gate"], p["up"], p["down"], weight.T))
    shared = mm(jax.nn.silu(mm(x, p["shared_gate"]))
                * mm(x, p["shared_up"]), p["shared_down"])
    return y + jax.nn.sigmoid(jnp.matmul(x, p["shared_router"])) * shared


def _layer(x, p, s, attention: bool, mm):
    mixer = _attention if attention else _deltanet
    x = x + mixer(_rms_norm(x, p["norm1"], s["eps"]), p["mixer"], s, mm)
    return x + _moe(_rms_norm(x, p["norm2"], s["eps"]), p["moe"], s, mm)


#: documents handed to the device in one call (each still runs alone: a
#: ``lax.map`` over them); their states are 4 MB a document at the
#: published widths
_DOCS_A_CALL = 64


def _embed(params, token_ids, lengths, config: dict, mm) -> np.ndarray:
    """Layer by layer (one layer's weights on the device at a time), one
    document at a time at the padded width: the model is causal, so what
    lies behind a document's last token does not reach it."""
    import jax
    import jax.numpy as jnp

    s = _sizes(config)
    ids = np.asarray(token_ids, np.int32)
    lens = np.asarray(lengths, np.int32)
    x = np.asarray(params["embed"], np.float32)[ids]          # (n, S, H)
    fns = {}
    with jax.default_matmul_precision("highest"):
        for i, layer in enumerate(params["layers"]):
            attention = (i + 1) % s["interval"] == 0
            if attention not in fns:
                fns[attention] = jax.jit(
                    lambda p, docs, attention=attention: jax.lax.map(
                        lambda a: _layer(a, p, s, attention, mm), docs))
            on_device = jax.device_put(layer)
            for d in range(0, len(x), _DOCS_A_CALL):
                x[d:d + _DOCS_A_CALL] = np.asarray(fns[attention](
                    on_device, jnp.asarray(x[d:d + _DOCS_A_CALL])))
            del on_device
        last = x[np.arange(len(x)), np.maximum(lens - 1, 0)]
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
    """:func:`embed` with every product of the mixers, of the experts and of
    attention's two in int8, one scale a tensor (an expert's matrix is a
    tensor of its own, as checkpoints keep it): the nearest precision below
    the bfloat16 the configuration serves in. The router, the norms, the
    softmax, the DeltaNet's gate and its state stay float32, as the
    configuration's ``serving`` keeps them: what a later PR that served in
    int8 would produce at best, and ``correct`` has to refuse it."""
    if kind not in CONTROL_KINDS:
        raise ValueError(f"unknown control {kind!r}")
    return _embed(params, token_ids, lengths, config, _int8_matmul)
