"""Pure-JAX hybrid decoder backbone as an embedder: a prefill-only forward
with last-token pooling, the way causal language models are used for
retrieval.

Pre-norm blocks ``x = x + mixer(norm1(x)); x = x + moe(norm2(x))`` with
RMSNorm and no biases; every layer's feed-forward is routed experts (top-k
of many, weights renormalised over the k). What a layer does follows from
:class:`DecoderConfig`, which takes the published keys of two families:

- Qwen3-Next (``config.json`` of ``Qwen/Qwen3-Next-80B-A3B-Instruct``; the
  defaults): zero-centred norms (``x / rms(x) * (1 + w)``); layer ``i`` is
  gated softmax attention with q/k norms and partial rotary positions where
  ``(i + 1) % full_attention_interval == 0`` and a Gated DeltaNet
  otherwise; SiLU experts plus one shared expert behind a sigmoid gate;
- SmallThinker (``PowerInfer/SmallThinker-21BA3B-Instruct``): plain norms
  (``x / rms(x) * w``); every layer attention with no gate and no q/k norm,
  a window of ``sliding_window_size`` keys where ``sliding_window_layout[i]``
  is 1 and full otherwise, rotary over the whole head where
  ``rope_layout[i]`` is 1 and no positions at all otherwise; ReGLU experts,
  no shared one, the router reading the mixer's input
  (``router_input="mixer_input"``).

- one layer function per kind: :func:`deltanet_layer`,
  :func:`attention_layer`, :func:`moe_layer`;
- :func:`moe_layer` is told the range of experts it holds
  (``config.experts_held``), routes over all and adds what its own experts
  give: under expert parallelism the shares of all chips, with the shared
  expert counted once, sum to the whole layer. On one chip it runs without
  the exchange, and nothing stands in for the absent chips;
- padded batches and ragged-packed rows (several documents back to back in
  a row) run the same forward: attention is causal within a document
  (``ops/attention.py`` ``segment_attention``, blocked: no score tensor of
  a row's length squared) with
  rotary positions restarting at each, the recurrent state and the
  convolution of a DeltaNet layer are reset at each document's first token
  (``position_ids == 0``);
- weights stay in the dtype they are given in (bfloat16 on the device);
  products run in ``compute_dtype`` with float32 accumulation; router,
  softmax, norms, the DeltaNet gate and its state are float32.

``lm_head`` and the multi-token-prediction layer take no part in an
embedding and are not here. The embedding is the final norm's state of a
document's last token, L2-normalised.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from pathway_tpu.ops import attention, deltanet, moe


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What the mixer of one layer is: ``"deltanet"``, or ``"attention"``
    with its window (None: full) and whether it rotates q and k."""

    mixer: str
    window: int | None = None
    rotary: bool = True


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    rms_norm_eps: float = 1e-6
    #: the norms' form: ``x / rms(x) * (1 + w)``, or ``x / rms(x) * w``
    zero_centred_norm: bool = True
    # attention
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    #: a sigmoid gate on the heads' output, from ``q_proj``'s second half
    attention_gate: bool = True
    #: RMSNorm over the head on q and k
    qk_norm: bool = True
    #: given, every layer is attention: layer ``i`` keeps a window of
    #: ``sliding_window_size`` keys where ``sliding_window_layout[i]`` is 1
    #: and rotates q and k where ``rope_layout[i]`` is 1
    sliding_window_layout: tuple[int, ...] | None = None
    rope_layout: tuple[int, ...] | None = None
    sliding_window_size: int | None = None
    # Gated DeltaNet
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # experts
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    #: None: no shared expert
    shared_expert_intermediate_size: int | None = 512
    norm_topk_prob: bool = True
    #: the experts' activation: ``"silu"``, ``"relu"``
    hidden_act: str = "silu"
    #: what the router reads: the expert layer's own normed input
    #: (``"moe_input"``) or the mixer's (``"mixer_input"``: the router
    #: placed before attention)
    router_input: str = "moe_input"
    #: the range [lo, hi) of routed experts this process holds (None: all)
    experts_held: tuple[int, int] | None = None
    max_len: int = 512
    #: read by the packer (xpacks/llm/embedders.py ``pack_ragged``), which
    #: hands the forward the offset of the token to pool; a padded batch
    #: pools a row's last real token
    pooling: str = "last"
    normalize: bool = True
    compute_dtype: Any = jnp.bfloat16

    @property
    def hidden(self) -> int:
        """The embedding's width (the embedder protocol's name for it)."""
        return self.hidden_size

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    def is_attention(self, layer: int) -> bool:
        return self.layer_kind(layer).mixer == "attention"

    def layer_kind(self, layer: int) -> LayerKind:
        if self.sliding_window_layout is not None:
            rotary = self.rope_layout is None or bool(self.rope_layout[layer])
            window = self.sliding_window_size \
                if self.sliding_window_layout[layer] else None
            return LayerKind("attention", window, rotary)
        if (layer + 1) % self.full_attention_interval == 0:
            return LayerKind("attention")
        return LayerKind("deltanet")

    @property
    def attention_windows(self) -> tuple:
        """Each attention layer's window, None for full attention (the
        packer counts a dispatch's attention work from it)."""
        kinds = map(self.layer_kind, range(self.num_hidden_layers))
        return tuple(k.window for k in kinds if k.mixer == "attention")

    @staticmethod
    def tiny(**kw) -> "DecoderConfig":
        """Small config for tests: one period, 8 experts top-2 (the
        Qwen3-Next pattern; :meth:`tiny_windowed` is the other)."""
        base = dict(vocab_size=2048, hidden_size=64, num_hidden_layers=4,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=32, linear_num_key_heads=2,
                    linear_num_value_heads=4, linear_key_head_dim=16,
                    linear_value_head_dim=16, num_experts=8,
                    num_experts_per_tok=2, moe_intermediate_size=32,
                    shared_expert_intermediate_size=32, max_len=128)
        base.update(kw)
        return DecoderConfig(**base)

    @staticmethod
    def tiny_windowed(**kw) -> "DecoderConfig":
        """Small config of the SmallThinker pattern: one period of a full
        layer without positions and three window layers with rotary, plain
        norms, 8 ReGLU experts top-2, no shared expert, the router on the
        mixer's input."""
        base = dict(zero_centred_norm=False, attention_gate=False,
                    qk_norm=False, partial_rotary_factor=1.0,
                    rope_theta=1.5e6, sliding_window_layout=(0, 1, 1, 1),
                    rope_layout=(0, 1, 1, 1), sliding_window_size=24,
                    shared_expert_intermediate_size=None, hidden_act="relu",
                    router_input="mixer_input")
        base.update(kw)
        return DecoderConfig.tiny(**base)

    # the embedder protocol: what JaxEncoderEmbedder calls on a config
    def encode(self, params, token_ids, attention_mask):
        return encode(params, token_ids, attention_mask, config=self)

    def encode_ragged(self, params, token_ids, doc_map, position_ids,
                      doc_seq, doc_off):
        return encode_ragged(params, token_ids, doc_map, position_ids,
                             doc_seq, doc_off, config=self)

    def init_params(self, key) -> dict:
        return init_params(key, self)

    def cost(self, batch: int, seq: int, *, ragged: bool):
        """Nothing for the engine's profiler: it has no model of a routed
        or recurrent layer's operations and bytes (the benchmark's is
        ``benchmark/models/qwen3_next.py`` ``dispatch_cost``)."""
        return None


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(key, config: DecoderConfig, dtype=jnp.float32) -> dict:
    """Seeded random weights in the program's tree (normal of deviation
    0.02; zero-centred norm weights zero, plain ones one; the DeltaNet's
    ``A_log`` the log of a uniform draw from (0, 16) and ``dt_bias`` ones,
    as the published code initialises them). A tree holds what the
    configuration's layers use: no gate's half of ``q_proj``, no q/k norm
    and no shared expert where the configuration has none."""
    c = config
    keys = iter(jax.random.split(key, 16 * c.num_hidden_layers + 2))

    def dense(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * 0.02).astype(dtype)

    h, nv, nk = c.hidden_size, c.linear_num_value_heads, c.linear_num_key_heads
    kd, vd = nk * c.linear_key_head_dim, nv * c.linear_value_head_dim
    lo, hi = c.held

    def norm(n):
        return (jnp.zeros if c.zero_centred_norm else jnp.ones)(
            (n,), jnp.float32)

    layers = []
    for i in range(c.num_hidden_layers):
        if c.is_attention(i):
            mixer = {
                "q_proj": dense(h, c.num_attention_heads * c.head_dim
                                * (2 if c.attention_gate else 1)),
                "k_proj": dense(h, c.num_key_value_heads * c.head_dim),
                "v_proj": dense(h, c.num_key_value_heads * c.head_dim),
                "o_proj": dense(c.num_attention_heads * c.head_dim, h)}
            if c.qk_norm:
                mixer.update(q_norm=norm(c.head_dim), k_norm=norm(c.head_dim))
        else:
            mixer = {
                "in_proj_qkvz": dense(h, 2 * kd + 2 * vd),
                "in_proj_ba": dense(h, 2 * nv),
                "conv": dense(c.linear_conv_kernel_dim, 2 * kd + vd),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (nv,), jnp.float32, 1e-4, 16.0)),
                "dt_bias": jnp.ones((nv,), jnp.float32),
                "norm": jnp.ones((c.linear_value_head_dim,), jnp.float32),
                "out_proj": dense(vd, h)}
        f, fs = c.moe_intermediate_size, c.shared_expert_intermediate_size
        experts = {"router": dense(h, c.num_experts),
                   "gate": dense(hi - lo, h, f), "up": dense(hi - lo, h, f),
                   "down": dense(hi - lo, f, h)}
        if fs is not None:
            experts.update(shared_gate=dense(h, fs), shared_up=dense(h, fs),
                           shared_down=dense(fs, h),
                           shared_router=dense(h, 1))
        layers.append({"norm1": norm(h), "norm2": norm(h), "mixer": mixer,
                       "moe": experts})
    return {"embed": dense(c.vocab_size, h), "layers": layers,
            "final_norm": norm(h)}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rms_norm(x, w, eps: float, zero_centred: bool = True):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    w = w.astype(jnp.float32)
    return xf * (1.0 + w if zero_centred else w)


def _proj(x, w, config: DecoderConfig):
    """``x @ w`` in the compute dtype, accumulated in float32."""
    cd = config.compute_dtype
    return jnp.matmul(x.astype(cd), w.astype(cd),
                      preferred_element_type=jnp.float32)


def _l2_normalise(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def deltanet_layer(x, p, pos, config: DecoderConfig, valid=None):
    """Gated DeltaNet mixer. x (B, T, H) normed input; pos (B, T) a
    token's position in its document (0 resets state and convolution);
    valid (B, T) False at padding, whose output nobody reads."""
    c = config
    b, t, _ = x.shape
    nk, nv = c.linear_num_key_heads, c.linear_num_value_heads
    dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
    kd, vd = nk * dk, nv * dv
    qkvz = _proj(x, p["in_proj_qkvz"], c)
    ba = _proj(x, p["in_proj_ba"], c)
    qkv, z = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
    qkv = jax.nn.silu(deltanet.causal_conv(qkv, p["conv"], pos))
    q = qkv[..., :kd].reshape(b, t, nk, dk)
    k = qkv[..., kd:2 * kd].reshape(b, t, nk, dk)
    v = qkv[..., 2 * kd:].reshape(b, t, nv, dv)
    beta = jax.nn.sigmoid(ba[..., :nv])
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., nv:] + p["dt_bias"].astype(jnp.float32))
    q = _l2_normalise(q) * dk ** -0.5
    k = _l2_normalise(k)
    # q and k stay with the key heads: each serves nv / nk value heads
    with jax.named_scope("decoder.deltanet.scan"):
        o = deltanet.gated_delta_rule(q, k, v, g, beta, pos == 0, valid)
    o = _rms_norm(o, p["norm"], c.rms_norm_eps, zero_centred=False)
    o = o * jax.nn.silu(z.reshape(b, t, nv, dv))
    return _proj(o.reshape(b, t, vd), p["out_proj"], c)


def _rotary(x, pos, rotary_dim: int, theta: float):
    """Rotate the first ``rotary_dim`` features of each head by the
    token's position (the half-split convention of the published code)."""
    half = rotary_dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = pos.astype(jnp.float32)[..., None, None] * freq   # (B,T,1,half)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention_layer(x, p, pos, seg, config: DecoderConfig,
                    kind: LayerKind = LayerKind("attention")):
    """Softmax attention, causal within a document, grouped heads; by the
    configuration with a sigmoid gate on the heads' output and RMSNorm on q
    and k, by the layer's ``kind`` with rotary positions and a window.
    seg (B, T): a token's document (-1 = padding)."""
    c = config
    b, t, _ = x.shape
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    cd = c.compute_dtype
    q = _proj(x, p["q_proj"], c)
    gate = None
    if c.attention_gate:
        q = q.reshape(b, t, nh, 2 * hd)
        q, gate = q[..., :hd], q[..., hd:]
    q = q.reshape(b, t, nh, hd)
    k = _proj(x, p["k_proj"], c).reshape(b, t, nkv, hd)
    v = _proj(x, p["v_proj"], c).reshape(b, t, nkv, hd)
    if c.qk_norm:
        q = _rms_norm(q, p["q_norm"], c.rms_norm_eps, c.zero_centred_norm)
        k = _rms_norm(k, p["k_norm"], c.rms_norm_eps, c.zero_centred_norm)
    if kind.rotary:
        rot = int(hd * c.partial_rotary_factor)
        q, k = (_rotary(a, pos, rot, c.rope_theta) for a in (q, k))
    scope = "decoder.attention.full" if kind.window is None \
        else "decoder.attention.window"
    with jax.named_scope(scope):
        o = attention.segment_attention(
            q.astype(cd), k.astype(cd), v.astype(cd), seg, pos,
            window=kind.window)
    if gate is not None:
        o = o * jax.nn.sigmoid(gate)
    return _proj(o.reshape(b, t, nh * hd), p["o_proj"], c)


def moe_layer(x, p, valid, config: DecoderConfig, router_x=None):
    """Routed experts (the held range's part) plus the shared expert, where
    the configuration has one. x (B, T, H) normed input; valid (B, T) False
    at padding; router_x (B, T, H): what the router reads (None: ``x``).
    Returns (y (B, T, H) float32, tokens each held expert took, what this
    execution adds to the pair buffer's counters: ``moe.buffer_use``)."""
    c = config
    b, t, h = x.shape
    flat = x.reshape(b * t, h).astype(c.compute_dtype)
    lo, hi = c.held
    lengths = moe.buffer_lengths(b * t * c.num_experts_per_tok,
                                 (hi - lo) / c.num_experts)
    with jax.named_scope("decoder.moe.route"):
        weights, experts = moe.route(
            flat if router_x is None else router_x.reshape(b * t, h),
            p["router"], c.num_experts_per_tok, c.norm_topk_prob)
    with jax.named_scope("decoder.moe.experts"):
        y, load = moe.grouped_experts(
            flat, weights, experts, p["gate"], p["up"], p["down"], c.held,
            valid.reshape(b * t), lengths, c.hidden_act)
        buffer = moe.buffer_use(load, lengths)
    if c.shared_expert_intermediate_size is not None:
        with jax.named_scope("decoder.moe.shared"):
            hidden = moe.ACTIVATIONS[c.hidden_act](
                _proj(flat, p["shared_gate"], c)) \
                * _proj(flat, p["shared_up"], c)
            y = y + _proj(hidden, p["shared_down"], c) * jax.nn.sigmoid(
                _proj(flat, p["shared_router"], c))
    return y.reshape(b, t, h), load, buffer


def _forward(params, token_ids, pos, seg, config: DecoderConfig):
    """Embedding + stack -> (final-norm hidden states (B, T, H) float32,
    the expert layers' counters summed over the layers:
    ``{"tokens_per_expert": (held,) int32, "buffer": (3,) float32
    [executions, those at the full length, pair-buffer rows]}``, which an
    embedder sums as its ``aux``)."""
    c = config
    norm = lambda x, w: _rms_norm(x, w, c.rms_norm_eps, c.zero_centred_norm)
    with jax.named_scope("decoder.embed"):
        x = params["embed"][token_ids].astype(jnp.float32)
    valid = seg >= 0
    lo, hi = c.held
    load = jnp.zeros((hi - lo,), jnp.int32)
    buffer = jnp.zeros((3,), jnp.float32)
    for i, layer in enumerate(params["layers"]):
        normed, kind = norm(x, layer["norm1"]), c.layer_kind(i)
        if kind.mixer == "attention":
            with jax.named_scope("decoder.attention"):
                x = x + attention_layer(normed, layer["mixer"], pos, seg, c,
                                        kind)
        else:
            with jax.named_scope("decoder.deltanet"):
                x = x + deltanet_layer(normed, layer["mixer"], pos, c,
                                       valid)
        y, took, used = moe_layer(
            norm(x, layer["norm2"]), layer["moe"], valid, c,
            normed if c.router_input == "mixer_input" else None)
        x, load, buffer = x + y, load + took, buffer + used
    return (norm(x, params["final_norm"]),
            {"tokens_per_expert": load, "buffer": buffer})


def _pool(x, rows, at, config: DecoderConfig):
    with jax.named_scope("decoder.pool"):
        pooled = x[rows, at]
        if config.normalize:
            pooled = pooled / jnp.maximum(
                jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)
        return pooled


def encode(params, token_ids, attention_mask, *, config: DecoderConfig):
    """Padded batch -> ((B, H) float32 embeddings, the expert layers'
    counters: :func:`_forward`).
    token_ids, attention_mask (B, T): a row is one document, its real
    tokens first."""
    b, t = token_ids.shape
    mask = attention_mask.astype(bool)
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    seg = jnp.where(mask, jnp.arange(b, dtype=jnp.int32)[:, None], -1)
    x, counters = _forward(params, token_ids.astype(jnp.int32), pos, seg,
                           config)
    last = jnp.maximum(jnp.sum(mask, axis=1) - 1, 0)
    return _pool(x, jnp.arange(b), last, config), counters


def encode_ragged(params, token_ids, doc_map, position_ids, doc_seq,
                  doc_off, *, config: DecoderConfig):
    """Ragged-packed rows -> ((n_docs, H) float32 embeddings, the expert
    layers' counters). The operands are ``models/encoder.py``
    ``encode_ragged``'s; ``doc_off`` is the offset of the token that is
    pooled, which under last-token pooling the packer sets to a document's
    last."""
    x, counters = _forward(params, token_ids.astype(jnp.int32),
                           position_ids, doc_map, config)
    return _pool(x, doc_seq, doc_off, config), counters
