"""Pure-JAX hybrid decoder backbone as an embedder: a prefill-only forward
with last-token pooling, the way causal language models are used for
retrieval.

Pre-norm blocks ``x = x + mixer(norm1(x)); x = x + moe(norm2(x))`` with
RMSNorm and no biases; every layer's feed-forward is routed experts (top-k
of many, weights renormalised over the k). What a layer does follows from
:class:`DecoderConfig`, which takes the published keys of three families:

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
  (``router_input="mixer_input"``);
- LongCat-Flash (``meituan-longcat/LongCat-Flash-Omni``'s language model;
  ``attention_method="MLA"``): plain norms; a layer is **two** latent
  attention sublayers, two dense SwiGLU feed-forwards and one expert layer
  whose input is the first sublayer's state and whose output joins the
  residual only at the layer's end (:func:`shortcut_layer`); the router's
  last ``zero_expert_num`` outputs are identity experts, its choice is over
  the probabilities plus a correction bias, its weights are the
  probabilities themselves times ``routed_scaling_factor``;
- GLM-5.2 (``zai-org/GLM-5.2``, ``glm_moe_dsa``; ``attention_method="MLA"``
  with ``indexer_types``): plain norms; a layer is one latent attention
  **over a learned choice of keys** and one feed-forward
  (:func:`indexed_layer`), dense or routed by ``mlp_layer_types[i]``; where
  ``indexer_types[i]`` is ``"full"`` the layer's indexer ranks every visible
  key for every query and keeps ``index_topk`` of them, where it is
  ``"shared"`` the layer attends over the choice of the nearest ``"full"``
  layer before it; the router scores with a sigmoid, chooses over the scores
  plus a correction bias, renormalises the chosen scores and scales them;
  the shared expert is added with no gate.

- one layer function per kind: :func:`deltanet_layer`,
  :func:`attention_layer`, :func:`latent_attention_layer`,
  :func:`dense_ffn`, :func:`moe_layer`;
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
    """What the mixer of one layer is: ``"deltanet"``, ``"attention"``
    with its window (None: full) and whether it rotates q and k,
    ``"latent"``: the shortcut-connected layer of two latent attention
    sublayers, or ``"indexed"``: latent attention over a learned choice of
    keys, which the layer's own indexer makes (``indexer`` ``"full"``) or
    the nearest such layer before it made (``"shared"``), before a
    feed-forward that is ``"dense"`` or ``"sparse"`` (routed experts)."""

    mixer: str
    window: int | None = None
    rotary: bool = True
    indexer: str | None = None
    mlp: str = "sparse"


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
    #: ``"MLA"``: every layer is :func:`shortcut_layer`, its attention
    #: latent: queries through a bottleneck of ``q_lora_rank``, keys and
    #: values expanded from one latent of ``kv_lora_rank`` a token, a head's
    #: key ``qk_nope_head_dim`` features of its own beside
    #: ``qk_rope_head_dim`` rotary ones shared by all heads, its value
    #: ``v_head_dim``; ``mla_scale_*``: the bottlenecks' outputs scaled by
    #: ``(hidden_size / rank) ** 0.5``
    attention_method: str | None = None
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    #: the width of each of a latent layer's two dense feed-forwards, and
    #: of an indexed layer's dense one
    ffn_hidden_size: int = 12288
    #: given (with ``attention_method="MLA"``), every layer is
    #: :func:`indexed_layer`: its feed-forward ``"dense"`` or ``"sparse"``
    #: by ``mlp_layer_types[i]`` (None: all sparse), its choice of keys its
    #: own indexer's (``"full"``: ``index_n_heads`` heads of
    #: ``index_head_dim`` features rank the visible keys of every query, the
    #: ``index_topk`` best are attended over) or the nearest full layer's
    #: before it (``"shared"``)
    indexer_types: tuple[str, ...] | None = None
    mlp_layer_types: tuple[str, ...] | None = None
    index_topk: int = 2048
    index_n_heads: int = 32
    index_head_dim: int = 128
    #: the leading dense layers of the published model: kept as published
    #: and not read (``mlp_layer_types`` is the list it expands to)
    first_k_dense_replace: int = 0
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
    #: given, the shared expert is ``n_shared_experts`` experts' width of
    #: ``moe_intermediate_size`` and is added with no gate
    n_shared_experts: int | None = None
    norm_topk_prob: bool = True
    #: the router's scores: ``"softmax"`` over its outputs, ``"sigmoid"`` of
    #: each alone
    scoring_func: str = "softmax"
    #: router outputs behind the ``num_experts`` with weights that return
    #: their input (``zero_expert_type`` "identity"): no product, no weights
    zero_expert_num: int = 0
    zero_expert_type: str = "identity"
    #: what the chosen probabilities are multiplied by
    routed_scaling_factor: float = 1.0
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

    @property
    def router_outputs(self) -> int:
        """Experts with weights and identity experts together."""
        return self.num_experts + self.zero_expert_num

    @property
    def shared_width(self) -> int | None:
        """The shared expert's width, None where the model has none."""
        if self.n_shared_experts is not None:
            return self.n_shared_experts * self.moe_intermediate_size
        return self.shared_expert_intermediate_size

    def layer_kind(self, layer: int) -> LayerKind:
        if self.attention_method == "MLA" and self.indexer_types is not None:
            indexer = self.indexer_types[layer]
            if indexer not in ("full", "shared") \
                    or "full" not in self.indexer_types[:layer + 1]:
                raise ValueError(
                    f"indexer_types[{layer}] {indexer!r}: \"full\", or "
                    f"\"shared\" behind a \"full\" layer, is what runs")
            mlp = "sparse" if self.mlp_layer_types is None \
                else self.mlp_layer_types[layer]
            return LayerKind("indexed", None, True, indexer, mlp)
        if self.attention_method is not None:
            if (self.attention_method, self.zero_expert_type) \
                    != ("MLA", "identity"):
                raise ValueError(
                    f"attention_method {self.attention_method!r} with "
                    f"zero_expert_type {self.zero_expert_type!r} and no "
                    f"indexer_types: \"MLA\" with \"identity\", or with "
                    f"indexer_types, is what runs")
            return LayerKind("latent", None, True)
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
        """Each attention core's window, None for full attention (the
        packer counts a dispatch's attention work from it): one a layer, a
        latent layer's two sublayers one each."""
        kinds = map(self.layer_kind, range(self.num_hidden_layers))
        return tuple(k.window for k in kinds if k.mixer != "deltanet"
                     for _ in range(2 if k.mixer == "latent" else 1))

    @property
    def attention_rep(self) -> int:
        """The query heads a key head of the attention cores serves (the
        packer counts the cores' tiles at the query block it decides,
        ``ops/attention.py`` ``block_sizes``): 1 for latent attention,
        whose every head has keys of its own."""
        if self.attention_method == "MLA":
            return 1
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def attention_index(self) -> tuple[int, int] | None:
        """(``index_topk``, the layers that hold an indexer) where the
        model chooses its keys (the packer counts the indexers' and the
        cores' work from it), else None."""
        if self.attention_method != "MLA" or self.indexer_types is None:
            return None
        return self.index_topk, self.indexer_types.count("full")

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

    @staticmethod
    def tiny_latent(**kw) -> "DecoderConfig":
        """Small config of the LongCat-Flash pattern: two layers of two
        latent attention sublayers (4 heads, keys 16 + 8, values 16), two
        dense feed-forwards and 8 experts with weights beside 4 identity
        experts, top-3, a correction bias, weights scaled and not
        renormalised."""
        base = dict(zero_centred_norm=False, rms_norm_eps=1e-5,
                    num_hidden_layers=2, attention_method="MLA",
                    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, ffn_hidden_size=96,
                    num_experts_per_tok=3, zero_expert_num=4,
                    routed_scaling_factor=6.0, norm_topk_prob=False,
                    shared_expert_intermediate_size=None)
        base.update(kw)
        return DecoderConfig.tiny(**base)

    @staticmethod
    def tiny_indexed(**kw) -> "DecoderConfig":
        """Small config of the GLM-5.2 pattern: a dense layer with an
        indexer, two expert layers that share its choice and one that
        chooses again; latent attention (4 heads, keys 16 + 8, values 32)
        over the 24 best keys of 2 index heads of 16 features; 8 experts
        top-2 behind a sigmoid router with a correction bias, renormalised
        and scaled by 2.5, one ungated shared expert."""
        base = dict(zero_centred_norm=False, rms_norm_eps=1e-5,
                    attention_method="MLA", q_lora_rank=24, kv_lora_rank=16,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=32,
                    mla_scale_q_lora=False, mla_scale_kv_lora=False,
                    ffn_hidden_size=96,
                    mlp_layer_types=("dense", "sparse", "sparse", "sparse"),
                    indexer_types=("full", "shared", "shared", "full"),
                    index_topk=24, index_n_heads=2, index_head_dim=16,
                    scoring_func="sigmoid", routed_scaling_factor=2.5,
                    shared_expert_intermediate_size=None, n_shared_experts=1)
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
    per_layer = 32 if c.attention_method is not None else 16
    keys = iter(jax.random.split(key, per_layer * c.num_hidden_layers + 2))

    def dense(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * 0.02).astype(dtype)

    h, nv, nk = c.hidden_size, c.linear_num_value_heads, c.linear_num_key_heads
    kd, vd = nk * c.linear_key_head_dim, nv * c.linear_value_head_dim
    lo, hi = c.held

    def norm(n):
        return (jnp.zeros if c.zero_centred_norm else jnp.ones)(
            (n,), jnp.float32)

    def experts():
        f, fs = c.moe_intermediate_size, c.shared_width
        tree = {"router": dense(h, c.router_outputs),
                "gate": dense(hi - lo, h, f), "up": dense(hi - lo, h, f),
                "down": dense(hi - lo, f, h)}
        if fs is not None:
            tree.update(shared_gate=dense(h, fs), shared_up=dense(h, fs),
                        shared_down=dense(fs, h))
            if c.n_shared_experts is None:
                tree["shared_router"] = dense(h, 1)
        return tree

    def latent():
        nh, dn = c.num_attention_heads, c.qk_nope_head_dim
        return {"q_a": dense(h, c.q_lora_rank), "q_norm": norm(c.q_lora_rank),
                "q_b": dense(c.q_lora_rank, nh * (dn + c.qk_rope_head_dim)),
                "kv_a": dense(h, c.kv_lora_rank + c.qk_rope_head_dim),
                "kv_norm": norm(c.kv_lora_rank),
                "kv_b": dense(c.kv_lora_rank, nh * (dn + c.v_head_dim)),
                "o": dense(nh * c.v_head_dim, h)}

    def ffn():
        return {"gate": dense(h, c.ffn_hidden_size),
                "up": dense(h, c.ffn_hidden_size),
                "down": dense(c.ffn_hidden_size, h)}

    def indexer():
        ni, di = c.index_n_heads, c.index_head_dim
        return {"q_b": dense(c.q_lora_rank, ni * di), "k": dense(h, di),
                "k_norm": jnp.ones((di,), jnp.float32),
                "k_bias": jnp.zeros((di,), jnp.float32), "w": dense(h, ni)}

    layers = []
    for i in range(c.num_hidden_layers):
        kind = c.layer_kind(i)
        if kind.mixer == "indexed":
            layer = {"norm1": norm(h), "norm2": norm(h), "mixer": latent()}
            if kind.indexer == "full":
                layer["mixer"]["indexer"] = indexer()
            if kind.mlp == "dense":
                layer["ffn"] = ffn()
            else:
                # a tenth of the latent family's deviation: a sigmoid's
                # scores lie closer together than a softmax's largest
                layer["moe"] = dict(experts(), bias=0.001 * jax.random.normal(
                    next(keys), (c.router_outputs,), jnp.float32))
            layers.append(layer)
            continue
        if kind.mixer == "latent":
            moe_tree = experts()
            # the correction bias: small beside a chosen probability
            moe_tree["bias"] = 0.01 * jax.random.normal(
                next(keys), (c.router_outputs,), jnp.float32)
            layers.append({"norm_in": [norm(h), norm(h)],
                           "norm_post": [norm(h), norm(h)],
                           "mixer": [latent(), latent()],
                           "ffn": [ffn(), ffn()], "moe": moe_tree})
            continue
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
        layers.append({"norm1": norm(h), "norm2": norm(h), "mixer": mixer,
                       "moe": experts()})
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


def _rotary_pairs(x, pos, theta: float):
    """Rotate every pair of neighbouring features ``(x[2i], x[2i + 1])`` of
    the last axis by the token's position (the interleaved convention of
    the latent-attention families). x (B, T, ..., d); pos (B, T)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = pos.astype(jnp.float32).reshape(
        pos.shape + (1,) * (x.ndim - 2)) * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def latent_attention_layer(x, p, pos, seg, config: DecoderConfig):
    """Multi-head latent attention in prefill, the unabsorbed form: queries
    through a bottleneck (``q_a``, norm, ``q_b``), keys and values expanded
    a head from one normed latent a token (``kv_a``, norm, ``kv_b``), a
    head's key its own ``qk_nope_head_dim`` features beside one rotary key
    of ``qk_rope_head_dim`` shared by all heads, values ``v_head_dim``
    wide, scores scaled by ``(nope + rope) ** -0.5``. (The absorbed form
    over the latent is a decode path and needs a cache this system does
    not keep.) x (B, T, H) normed; seg (B, T): a token's document."""
    c = config
    b, t, h = x.shape
    nh, dn, dr, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                      c.qk_rope_head_dim, c.v_head_dim)
    cd = c.compute_dtype
    with jax.named_scope("decoder.attention.latent"):
        q = _rms_norm(_proj(x, p["q_a"], c), p["q_norm"], c.rms_norm_eps,
                      c.zero_centred_norm)
        q = _proj(q, p["q_b"], c).reshape(b, t, nh, dn + dr)
        if c.mla_scale_q_lora:
            q = q * (h / c.q_lora_rank) ** 0.5
        kv = _proj(x, p["kv_a"], c)
        latent = _rms_norm(kv[..., :c.kv_lora_rank], p["kv_norm"],
                           c.rms_norm_eps, c.zero_centred_norm)
        if c.mla_scale_kv_lora:
            latent = latent * (h / c.kv_lora_rank) ** 0.5
        # no arithmetic follows on a head's own keys and its values: they
        # leave the product in the compute dtype (512 MB of float32 less
        # at 8,192 tokens)
        kv_heads = _proj(latent, p["kv_b"], c).astype(cd).reshape(
            b, t, nh, dn + dv)
        q_rope = _rotary_pairs(q[..., dn:], pos, c.rope_theta)
        k_rope = _rotary_pairs(kv[..., c.kv_lora_rank:], pos, c.rope_theta)
    with jax.named_scope("decoder.attention.full"):
        o = attention.latent_attention(
            q[..., :dn].astype(cd), q_rope.astype(cd), kv_heads[..., :dn],
            k_rope.astype(cd), kv_heads[..., dn:], seg, pos,
            scale=(dn + dr) ** -0.5)
    return _proj(o.reshape(b, t, nh * dv), p["o"], c)


#: heads whose queries, keys and values an indexed layer expands at a time.
#: All 64 heads of a row of 16,384 tokens at once are 2.0 GB of bfloat16
#: queries, keys and values beside 1.1 GB of float32 output; a quarter of
#: them at a time leaves the chip room for the weights and the index
HEAD_GROUP = 16


def _layer_norm(x, w, b, eps: float = 1e-6):
    """LayerNorm over the last axis, float32."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return xf * w.astype(jnp.float32) + b.astype(jnp.float32)


def _rotary_first(x, pos, rotary_dim: int, theta: float):
    """:func:`_rotary_pairs` over the first ``rotary_dim`` features of the
    last axis, the rest as they are."""
    return jnp.concatenate(
        [_rotary_pairs(x[..., :rotary_dim], pos, theta),
         x[..., rotary_dim:]], axis=-1)


def choose_keys(x, q_latent, p, pos, seg, config: DecoderConfig):
    """A layer's indexer: which keys each query attends over
    (``ops/attention.py`` ``select_keys``). Index queries of
    ``index_n_heads`` heads from the queries' normed latent ``q_latent``,
    one LayerNormed index key a token and the heads' weights from the
    layer's input ``x``, queries and key rotated over their first
    ``qk_rope_head_dim`` features; products in the compute dtype, the
    weights, ReLU, sum and choice float32. Returns (the choice, the float32
    count of chosen pairs)."""
    c = config
    b, t, _ = x.shape
    ni, di, rot = c.index_n_heads, c.index_head_dim, c.qk_rope_head_dim
    cd = c.compute_dtype
    q = _rotary_first(_proj(q_latent, p["q_b"], c).reshape(b, t, ni, di),
                      pos, rot, c.rope_theta)
    k = _rotary_first(_layer_norm(_proj(x, p["k"], c), p["k_norm"],
                                  p["k_bias"]), pos, rot, c.rope_theta)
    w = _proj(x, p["w"], c) * (ni ** -0.5 * di ** -0.5)
    return attention.select_keys(q.astype(cd), k.astype(cd), w, seg, pos,
                                 topk=c.index_topk)


def indexed_attention_layer(x, p, pos, seg, config: DecoderConfig,
                            choice=None):
    """Latent attention (:func:`latent_attention_layer`'s form: unabsorbed,
    one rotary key a token) over a learned choice of keys: the layer's own
    indexer's (``p["indexer"]``, where ``choice`` is None) or the one handed
    in, which an earlier layer made. :data:`HEAD_GROUP` heads at a time:
    their queries, keys and values are expanded, attended and projected
    back before the next group's. Returns (y (B, T, H) float32, the choice
    attended over, the float32 count of its chosen pairs or None where it
    was handed in)."""
    c = config
    b, t, h = x.shape
    nh, dn, dr, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                      c.qk_rope_head_dim, c.v_head_dim)
    cd = c.compute_dtype
    with jax.named_scope("decoder.attention.latent"):
        q_latent = _rms_norm(_proj(x, p["q_a"], c), p["q_norm"],
                             c.rms_norm_eps, c.zero_centred_norm)
        if c.mla_scale_q_lora:
            q_latent = q_latent * (h / c.q_lora_rank) ** 0.5
        kv = _proj(x, p["kv_a"], c)
        latent = _rms_norm(kv[..., :c.kv_lora_rank], p["kv_norm"],
                           c.rms_norm_eps, c.zero_centred_norm)
        if c.mla_scale_kv_lora:
            latent = latent * (h / c.kv_lora_rank) ** 0.5
        k_rope = _rotary_pairs(kv[..., c.kv_lora_rank:], pos,
                               c.rope_theta).astype(cd)
    chosen = None
    if choice is None:
        with jax.named_scope("decoder.attention.index"):
            choice, chosen = choose_keys(x, q_latent, p["indexer"], pos, seg,
                                         c)

    def heads(y, ws):
        """``y`` plus what the heads of ``ws`` (their columns of ``q_b``
        and ``kv_b``, their rows of ``o``) give."""
        q_b, kv_b, o_w = ws
        g = o_w.shape[0] // dv
        with jax.named_scope("decoder.attention.latent"):
            q = _proj(q_latent, q_b, c).reshape(b, t, g, dn + dr)
            kv_heads = _proj(latent, kv_b, c).astype(cd).reshape(
                b, t, g, dn + dv)
            q_rope = _rotary_pairs(q[..., dn:], pos, c.rope_theta)
        with jax.named_scope("decoder.attention.sparse"):
            o = attention.latent_attention(
                q[..., :dn].astype(cd), q_rope.astype(cd),
                kv_heads[..., :dn], k_rope, kv_heads[..., dn:], seg, pos,
                scale=(dn + dr) ** -0.5, choice=choice)
        return y + _proj(o.reshape(b, t, g * dv), o_w, c)

    groups = nh // HEAD_GROUP if nh % HEAD_GROUP == 0 else 1
    weights = (p["q_b"], p["kv_b"], p["o"])
    y = jnp.zeros((b, t, h), jnp.float32)
    if groups == 1:
        return heads(y, weights), choice, chosen
    # a group's columns of the two expansions side by side, (groups, rank,
    # heads x width); ``o``'s rows are a group's already
    by_group = lambda w: jnp.swapaxes(
        w.reshape(w.shape[0], groups, -1), 0, 1)
    y, _ = jax.lax.scan(
        lambda y, ws: (heads(y, ws), None), y,
        (by_group(p["q_b"]), by_group(p["kv_b"]),
         p["o"].reshape(groups, -1, h)))
    return y, choice, chosen


def dense_ffn(x, p, config: DecoderConfig):
    """A dense gated feed-forward, ``W_down(act(W_gate x) * (W_up x))``."""
    hidden = moe.ACTIVATIONS[config.hidden_act](
        _proj(x, p["gate"], config)) * _proj(x, p["up"], config)
    return _proj(hidden, p["down"], config)


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
    Returns (y (B, T, H) float32, this execution's counters:
    ``tokens_per_expert`` each held expert took, ``buffer`` what it adds to
    the pair buffer's counters (``moe.buffer_use``) and, where the router
    has identity experts, ``zero_pairs`` and ``pairs``: the chosen pairs of
    real tokens that took one, and all of them)."""
    c = config
    b, t, h = x.shape
    flat = x.reshape(b * t, h).astype(c.compute_dtype)
    lo, hi = c.held
    lengths = moe.buffer_lengths(b * t * c.num_experts_per_tok,
                                 (hi - lo) / c.router_outputs)
    with jax.named_scope("decoder.moe.route"):
        weights, experts = moe.route(
            flat if router_x is None else router_x.reshape(b * t, h),
            p["router"], c.num_experts_per_tok, c.norm_topk_prob,
            p.get("bias"), c.routed_scaling_factor, c.scoring_func)
    with jax.named_scope("decoder.moe.experts"):
        y, load = moe.grouped_experts(
            flat, weights, experts, p["gate"], p["up"], p["down"], c.held,
            valid.reshape(b * t), lengths, c.hidden_act)
        counters = {"tokens_per_expert": load,
                    "buffer": moe.buffer_use(load, lengths)}
        if c.zero_expert_num:
            same, (counters["zero_pairs"], counters["pairs"]) = \
                moe.identity_part(flat, weights, experts, c.num_experts,
                                  valid.reshape(b * t))
            y = y + same
    if c.shared_width is not None:
        with jax.named_scope("decoder.moe.shared"):
            hidden = moe.ACTIVATIONS[c.hidden_act](
                _proj(flat, p["shared_gate"], c)) \
                * _proj(flat, p["shared_up"], c)
            shared = _proj(hidden, p["shared_down"], c)
            if c.n_shared_experts is None:
                shared = shared * jax.nn.sigmoid(
                    _proj(flat, p["shared_router"], c))
            y = y + shared
    return y.reshape(b, t, h), counters


def shortcut_layer(x, p, pos, seg, valid, config: DecoderConfig):
    """One layer of the LongCat-Flash pattern: two latent attention
    sublayers, two dense feed-forwards, and one expert layer that reads the
    first sublayer's normed state and joins the residual only at the
    layer's end (shortcut-connected: nothing between depends on it, so the
    compiler is free to run it beside the second sublayer)::

        h = x + MLA_0(norm_in0(x));  a = norm_post0(h);  s = MoE(a)
        h = h + FFN_0(a)
        h = h + MLA_1(norm_in1(h))
        y = h + FFN_1(norm_post1(h)) + s

    Returns (y (B, T, H) float32, the expert layer's counters)."""
    c = config
    norm = lambda x, w: _rms_norm(x, w, c.rms_norm_eps, c.zero_centred_norm)
    shortcut = counters = None
    for i in range(2):
        with jax.named_scope("decoder.attention"):
            x = x + latent_attention_layer(norm(x, p["norm_in"][i]),
                                           p["mixer"][i], pos, seg, c)
        normed = norm(x, p["norm_post"][i])
        if i == 0:
            shortcut, counters = moe_layer(normed, p["moe"], valid, c)
        with jax.named_scope("decoder.ffn"):
            x = x + dense_ffn(normed, p["ffn"][i], c)
    return x + shortcut, counters


def indexed_layer(x, p, pos, seg, valid, config: DecoderConfig,
                  kind: LayerKind, choice=None):
    """One layer of the GLM-5.2 pattern, plain pre-norm::

        h = x + MLA(norm1(x))        over the layer's choice of keys
        y = h + FFN(norm2(h))        dense, or routed experts and a shared one

    ``choice``: the nearest full layer's before this one, which a
    ``"shared"`` layer attends over (a ``"full"`` layer makes its own and
    does not read it). Returns (y (B, T, H) float32, the expert layer's
    counters or None for a dense layer, the choice attended over, the count
    of its chosen pairs)."""
    c = config
    norm = lambda x, w: _rms_norm(x, w, c.rms_norm_eps, c.zero_centred_norm)
    with jax.named_scope("decoder.attention"):
        y, choice, chosen = indexed_attention_layer(
            norm(x, p["norm1"]), p["mixer"], pos, seg, c,
            choice if kind.indexer == "shared" else None)
        x = x + y
    normed = norm(x, p["norm2"])
    if kind.mlp == "dense":
        with jax.named_scope("decoder.ffn"):
            return x + dense_ffn(normed, p["ffn"], c), None, choice, chosen
    y, counters = moe_layer(normed, p["moe"], valid, c)
    return x + y, counters, choice, chosen


def _forward(params, token_ids, pos, seg, config: DecoderConfig):
    """Embedding + stack -> (final-norm hidden states (B, T, H) float32,
    the expert layers' counters summed over the layers:
    ``{"tokens_per_expert": (held,) int32, "buffer": (3,) float32
    [executions, those at the full length, pair-buffer rows]}`` and, for a
    router with identity experts, ``"zero_pairs"`` and ``"pairs"`` (float32
    scalars), and for a model that chooses its keys ``"selected_pairs"``
    and ``"visible_pairs"``: the (query, key) pairs its attention layers
    attended over, counted from the choices themselves, and the pairs they
    could see (float32 scalars), which an embedder sums as its ``aux``)."""
    c = config
    norm = lambda x, w: _rms_norm(x, w, c.rms_norm_eps, c.zero_centred_norm)
    with jax.named_scope("decoder.embed"):
        x = params["embed"][token_ids].astype(jnp.float32)
    valid = seg >= 0
    counters = None
    # the choice of keys the last full indexer made, the count of its
    # pairs, and the pairs attended over so far
    choice = chosen = None
    selected = jnp.float32(0.0)
    for i, layer in enumerate(params["layers"]):
        kind = c.layer_kind(i)
        if kind.mixer == "indexed":
            # a layer's weights wait for its input, as a latent layer's
            x, layer = jax.lax.optimization_barrier((x, layer))
            x, used, choice, made = indexed_layer(x, layer, pos, seg, valid,
                                                  c, kind, choice)
            chosen = chosen if made is None else made
            selected = selected + chosen
            if used is None:
                continue
        elif kind.mixer == "latent":
            # a layer's weights wait for the layer's input. Left free, the
            # TPU's compiler re-lays every layer's projection weights at
            # the program's start and keeps all the copies: 4.0 GiB of
            # temporaries for 1.9 at four layers of the published widths,
            # more than the weights and an index leave of the chip
            # (compiled for the described chip, PR 35). Inside a layer
            # nothing is pinned
            x, layer = jax.lax.optimization_barrier((x, layer))
            x, used = shortcut_layer(x, layer, pos, seg, valid, c)
        else:
            normed = norm(x, layer["norm1"])
            if kind.mixer == "attention":
                with jax.named_scope("decoder.attention"):
                    x = x + attention_layer(normed, layer["mixer"], pos,
                                            seg, c, kind)
            else:
                with jax.named_scope("decoder.deltanet"):
                    x = x + deltanet_layer(normed, layer["mixer"], pos, c,
                                           valid)
            y, used = moe_layer(
                norm(x, layer["norm2"]), layer["moe"], valid, c,
                normed if c.router_input == "mixer_input" else None)
            x = x + y
        counters = used if counters is None else jax.tree.map(
            jnp.add, counters, used)
    if choice is not None and counters is not None:
        # beside the expert layers' counters (a model with none returns no
        # ``aux`` at all)
        visible = len(c.attention_windows) * jnp.sum(
            jnp.where(valid, pos + 1, 0).astype(jnp.float32))
        counters = dict(counters, selected_pairs=selected,
                        visible_pairs=visible)
    return norm(x, params["final_norm"]), counters


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
