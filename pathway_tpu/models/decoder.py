"""Pure-JAX hybrid decoder backbone as an embedder: a prefill-only forward
with last-token pooling, the way causal language models are used for
retrieval.

The shape is the one published for Qwen3-Next (``config.json`` of
``Qwen/Qwen3-Next-80B-A3B-Instruct``; :class:`DecoderConfig` takes the
published keys): pre-norm blocks ``x = x + mixer(norm1(x)); x = x +
moe(norm2(x))`` with zero-centred RMSNorm (``x / rms(x) * (1 + w)``), no
biases. Layer ``i`` is gated softmax attention where ``(i + 1) %
full_attention_interval == 0`` and a Gated DeltaNet otherwise; every layer's
feed-forward is routed experts (top-k of many, weights renormalised over the
k) plus one shared expert behind a sigmoid gate.

- one layer function per kind: :func:`deltanet_layer`,
  :func:`attention_layer`, :func:`moe_layer`;
- :func:`moe_layer` is told the range of experts it holds
  (``config.experts_held``), routes over all and adds what its own experts
  give: under expert parallelism the shares of all chips, with the shared
  expert counted once, sum to the whole layer. On one chip it runs without
  the exchange, and nothing stands in for the absent chips;
- padded batches and ragged-packed rows (several documents back to back in
  a row) run the same forward: attention is causal within a document with
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

from pathway_tpu.ops import deltanet, moe


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    rms_norm_eps: float = 1e-6
    # gated attention
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
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
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
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
        return (layer + 1) % self.full_attention_interval == 0

    @staticmethod
    def tiny(**kw) -> "DecoderConfig":
        """Small config for tests: one period, 8 experts top-2."""
        base = dict(vocab_size=2048, hidden_size=64, num_hidden_layers=4,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=32, linear_num_key_heads=2,
                    linear_num_value_heads=4, linear_key_head_dim=16,
                    linear_value_head_dim=16, num_experts=8,
                    num_experts_per_tok=2, moe_intermediate_size=32,
                    shared_expert_intermediate_size=32, max_len=128)
        base.update(kw)
        return DecoderConfig(**base)

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
    0.02; zero-centred norm weights zero; the DeltaNet's ``A_log`` the log
    of a uniform draw from (0, 16) and ``dt_bias`` ones, as the published
    code initialises them)."""
    c = config
    keys = iter(jax.random.split(key, 16 * c.num_hidden_layers + 2))

    def dense(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * 0.02).astype(dtype)

    h, nv, nk = c.hidden_size, c.linear_num_value_heads, c.linear_num_key_heads
    kd, vd = nk * c.linear_key_head_dim, nv * c.linear_value_head_dim
    lo, hi = c.held
    layers = []
    for i in range(c.num_hidden_layers):
        if c.is_attention(i):
            mixer = {
                "q_proj": dense(h, c.num_attention_heads * 2 * c.head_dim),
                "k_proj": dense(h, c.num_key_value_heads * c.head_dim),
                "v_proj": dense(h, c.num_key_value_heads * c.head_dim),
                "q_norm": jnp.zeros((c.head_dim,), jnp.float32),
                "k_norm": jnp.zeros((c.head_dim,), jnp.float32),
                "o_proj": dense(c.num_attention_heads * c.head_dim, h)}
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
        layers.append({
            "norm1": jnp.zeros((h,), jnp.float32),
            "norm2": jnp.zeros((h,), jnp.float32),
            "mixer": mixer,
            "moe": {"router": dense(h, c.num_experts),
                    "gate": dense(hi - lo, h, f), "up": dense(hi - lo, h, f),
                    "down": dense(hi - lo, f, h),
                    "shared_gate": dense(h, fs), "shared_up": dense(h, fs),
                    "shared_down": dense(fs, h),
                    "shared_router": dense(h, 1)}})
    return {"embed": dense(c.vocab_size, h), "layers": layers,
            "final_norm": jnp.zeros((h,), jnp.float32)}


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


def attention_layer(x, p, pos, seg, config: DecoderConfig):
    """Gated softmax attention, causal within a document, grouped heads.
    seg (B, T): a token's document (-1 = padding)."""
    c = config
    b, t, _ = x.shape
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    cd = c.compute_dtype
    qg = _proj(x, p["q_proj"], c).reshape(b, t, nh, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = _proj(x, p["k_proj"], c).reshape(b, t, nkv, hd)
    v = _proj(x, p["v_proj"], c).reshape(b, t, nkv, hd)
    rot = int(hd * c.partial_rotary_factor)
    q = _rotary(_rms_norm(q, p["q_norm"], c.rms_norm_eps), pos, rot,
                c.rope_theta)
    k = _rotary(_rms_norm(k, p["k_norm"], c.rms_norm_eps), pos, rot,
                c.rope_theta)
    q = q.reshape(b, t, nkv, nh // nkv, hd)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q.astype(cd), k.astype(cd),
                        preferred_element_type=jnp.float32) * hd ** -0.5
    at = jnp.arange(t)
    see = (seg[:, :, None] == seg[:, None, :]) & (seg >= 0)[:, None, :] \
        & (at[None, :, None] >= at[None, None, :])
    scores = jnp.where(see[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(cd), v.astype(cd),
                   preferred_element_type=jnp.float32)
    o = o.reshape(b, t, nh, hd) * jax.nn.sigmoid(gate)
    return _proj(o.reshape(b, t, nh * hd), p["o_proj"], c)


def moe_layer(x, p, valid, config: DecoderConfig):
    """Routed experts (the held range's part) plus the shared expert.
    x (B, T, H) normed input; valid (B, T) False at padding. Returns
    (y (B, T, H) float32, tokens each held expert took, what this
    execution adds to the pair buffer's counters: ``moe.buffer_use``)."""
    c = config
    b, t, h = x.shape
    flat = x.reshape(b * t, h).astype(c.compute_dtype)
    lo, hi = c.held
    lengths = moe.buffer_lengths(b * t * c.num_experts_per_tok,
                                 (hi - lo) / c.num_experts)
    with jax.named_scope("decoder.moe.route"):
        weights, experts = moe.route(flat, p["router"],
                                     c.num_experts_per_tok, c.norm_topk_prob)
    with jax.named_scope("decoder.moe.experts"):
        routed, load = moe.grouped_experts(
            flat, weights, experts, p["gate"], p["up"], p["down"], c.held,
            valid.reshape(b * t), lengths)
        buffer = moe.buffer_use(load, lengths)
    with jax.named_scope("decoder.moe.shared"):
        hidden = jax.nn.silu(_proj(flat, p["shared_gate"], c)) \
            * _proj(flat, p["shared_up"], c)
        shared = _proj(hidden, p["shared_down"], c) * jax.nn.sigmoid(
            _proj(flat, p["shared_router"], c))
    return (routed + shared).reshape(b, t, h), load, buffer


def _forward(params, token_ids, pos, seg, config: DecoderConfig):
    """Embedding + stack -> (final-norm hidden states (B, T, H) float32,
    the expert layers' counters summed over the layers:
    ``{"tokens_per_expert": (held,) int32, "buffer": (3,) float32
    [executions, those at the full length, pair-buffer rows]}``, which an
    embedder sums as its ``aux``)."""
    c = config
    with jax.named_scope("decoder.embed"):
        x = params["embed"][token_ids].astype(jnp.float32)
    valid = seg >= 0
    lo, hi = c.held
    load = jnp.zeros((hi - lo,), jnp.int32)
    buffer = jnp.zeros((3,), jnp.float32)
    for i, layer in enumerate(params["layers"]):
        normed = _rms_norm(x, layer["norm1"], c.rms_norm_eps)
        if c.is_attention(i):
            with jax.named_scope("decoder.attention"):
                x = x + attention_layer(normed, layer["mixer"], pos, seg, c)
        else:
            with jax.named_scope("decoder.deltanet"):
                x = x + deltanet_layer(normed, layer["mixer"], pos, c,
                                       valid)
        y, took, used = moe_layer(
            _rms_norm(x, layer["norm2"], c.rms_norm_eps), layer["moe"],
            valid, c)
        x, load, buffer = x + y, load + took, buffer + used
    return (_rms_norm(x, params["final_norm"], c.rms_norm_eps),
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
