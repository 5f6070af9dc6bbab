"""Pure-JAX transformer encoder — the flagship embedder model.

A BERT-family bidirectional encoder (default shape = BGE-small-en-v1.5:
vocab 30522, hidden 384, 12 layers, 12 heads) replacing the reference's
torch SentenceTransformerEmbedder (xpacks/llm/embedders.py:268-326) with a
TPU-first design:

- params are a plain pytree of jnp arrays; ``param_pspecs`` gives the
  matching ``PartitionSpec`` tree for Megatron-style tensor parallelism
  over the mesh ``model`` axis (QKV/up-proj split on the output dim,
  out-proj/down-proj on the input dim — XLA/GSPMD inserts the psums);
- compute in bfloat16 (MXU native), accumulation/layernorm in float32;
- no data-dependent control flow: one jit-compiled ``encode`` per
  (batch, seq) bucket;
- optional mixture-of-experts MLP (expert-parallel over the ``model``
  axis) and a pluggable attention hook so long sequences can run
  ring/Ulysses sequence-parallel attention
  (pathway_tpu/parallel/ring_attention.py).

Post-layernorm residual layout matches BERT so real BGE/MiniLM checkpoints
load directly (see pathway_tpu/models/hf_loader.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from pathway_tpu.parallel.mesh import MODEL_AXIS


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    vocab_size: int = 30522
    hidden: int = 384
    layers: int = 12
    heads: int = 12
    intermediate: int = 1536
    max_len: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    pooling: str = "cls"  # "cls" (BGE) | "mean" (MiniLM/ST default)
    normalize: bool = True
    num_experts: int = 0  # 0 → dense MLP; >0 → top-1 switch MoE
    compute_dtype: Any = jnp.bfloat16
    # "auto": tanh-gelu under bf16 compute, erf-gelu under f32. erf's
    # lowering kept XLA from fusing/tiling the MLP block on a v5e (seen
    # through an earlier set-up that no longer exists, at 6 heads; the
    # speed difference is not measured on the current machine), while
    # tanh's approximation error (≤3e-3 abs) is BELOW bf16's own
    # quantization step, so within bf16 the swap is numerically free
    # (cos(erf,tanh) ≥ 0.99993 vs cos(f32,bf16) ≥ 0.99988 end-to-end).
    # f32 compute keeps erf: checkpoint-golden parity at
    # rtol 2e-4 (tests/test_hf_loader.py) needs BERT's exact activation.
    gelu: str = "auto"  # "auto" | "erf" | "tanh"

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    # what an embedder calls on a config, whatever the architecture
    # (xpacks/llm/embedders.py JaxEncoderEmbedder)
    def encode(self, params, token_ids, attention_mask):
        return encode(params, token_ids, attention_mask, config=self)

    def encode_ragged(self, params, token_ids, doc_map, position_ids,
                      doc_seq, doc_off):
        return encode_ragged(params, token_ids, doc_map, position_ids,
                             doc_seq, doc_off, config=self)

    def init_params(self, key) -> dict:
        return init_params(key, self)

    def cost(self, batch: int, seq: int, *, ragged: bool):
        """(kernel name, flops, bytes) of one forward of ``batch x seq``
        token slots for the engine's profiler, or None where a model has
        no such reckoning."""
        from pathway_tpu.engine.profiler import encoder_cost, \
            segment_attention_cost

        name, fn = (("segment_attention", segment_attention_cost) if ragged
                    else ("encoder_forward", encoder_cost))
        return (name, *fn(batch, seq, hidden=self.hidden,
                          intermediate=self.intermediate,
                          layers=self.layers))

    @staticmethod
    def tiny(**kw) -> "EncoderConfig":
        """Small config for tests/dryruns."""
        base = dict(vocab_size=1024, hidden=64, layers=2, heads=4,
                    intermediate=128, max_len=128)
        base.update(kw)
        return EncoderConfig(**base)

    @staticmethod
    def bge_small(**kw) -> "EncoderConfig":
        """BAAI/bge-small-en-v1.5 at its published shape. Source: the
        model's ``config.json`` on the Hugging Face hub (BertModel:
        vocab_size 30522, hidden_size 384, num_hidden_layers 12,
        num_attention_heads 12, intermediate_size 1536,
        max_position_embeddings 512, type_vocab_size 2, layer_norm_eps
        1e-12) — the fields ``hf_loader.load_model`` reads from a
        checkpoint. Written from the published file; there is no network
        here to fetch it again."""
        return EncoderConfig(**kw)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

DENSE_INIT_SCALE = 0.02


def _dense_init(key, shape, scale=DENSE_INIT_SCALE):
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale)


def _build_params(config: EncoderConfig, dense, zeros, ones) -> dict:
    """Parameter tree structure, parametric over the array factory — the
    ONE place the encoder's shapes live (jax and host inits share it)."""
    H, I_, V = config.hidden, config.intermediate, config.vocab_size
    params: dict[str, Any] = {
        "embeddings": {
            "token": dense((V, H)),
            "position": dense((config.max_len, H)),
            "token_type": dense((config.type_vocab_size, H)),
            "ln_scale": ones((H,)),
            "ln_bias": zeros((H,)),
        },
        "layers": [],
    }
    for _ in range(config.layers):
        layer = {
            "attn": {
                "wq": dense((H, H)), "bq": zeros((H,)),
                "wk": dense((H, H)), "bk": zeros((H,)),
                "wv": dense((H, H)), "bv": zeros((H,)),
                "wo": dense((H, H)), "bo": zeros((H,)),
                "ln_scale": ones((H,)),
                "ln_bias": zeros((H,)),
            },
        }
        if config.num_experts > 0:
            E = config.num_experts
            layer["moe"] = {
                "router": dense((H, E)),
                "w1": dense((E, H, I_)),
                "b1": zeros((E, I_)),
                "w2": dense((E, I_, H)),
                "b2": zeros((E, H)),
                "ln_scale": ones((H,)),
                "ln_bias": zeros((H,)),
            }
        else:
            layer["mlp"] = {
                "w1": dense((H, I_)),
                "b1": zeros((I_,)),
                "w2": dense((I_, H)),
                "b2": zeros((H,)),
                "ln_scale": ones((H,)),
                "ln_bias": zeros((H,)),
            }
        params["layers"].append(layer)
    return params


def init_params(key, config: EncoderConfig) -> dict:
    keys = iter(jax.random.split(key, 16 + config.layers * 16))
    return _build_params(
        config,
        dense=lambda shape: _dense_init(next(keys), shape),
        zeros=lambda shape: jnp.zeros(shape, jnp.float32),
        ones=lambda shape: jnp.ones(shape, jnp.float32))


def init_params_host(seed: int, config: EncoderConfig) -> dict:
    """init_params twin on numpy: same tree/shapes, host arrays, ZERO jax
    backend touch — for driver entry points that must not pick a backend
    themselves (the caller's jit moves the arrays)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return _build_params(
        config,
        dense=lambda shape: (rng.normal(size=shape)
                             * DENSE_INIT_SCALE).astype(np.float32),
        zeros=lambda shape: np.zeros(shape, np.float32),
        ones=lambda shape: np.ones(shape, np.float32))


def param_pspecs(config: EncoderConfig) -> dict:
    """PartitionSpec tree for tensor parallelism over the ``model`` axis."""
    M = MODEL_AXIS
    emb = {
        "token": P(None, None),
        "position": P(None, None),
        "token_type": P(None, None),
        "ln_scale": P(None),
        "ln_bias": P(None),
    }
    layers = []
    for _ in range(config.layers):
        layer = {
            "attn": {
                # QKV split on the head (output) dim, out-proj on input dim
                "wq": P(None, M), "bq": P(M),
                "wk": P(None, M), "bk": P(M),
                "wv": P(None, M), "bv": P(M),
                "wo": P(M, None), "bo": P(None),
                "ln_scale": P(None), "ln_bias": P(None),
            },
        }
        if config.num_experts > 0:
            layer["moe"] = {
                "router": P(None, None),
                # expert-parallel: experts sharded over the model axis
                "w1": P(M, None, None), "b1": P(M, None),
                "w2": P(M, None, None), "b2": P(M, None),
                "ln_scale": P(None), "ln_bias": P(None),
            }
        else:
            layer["mlp"] = {
                "w1": P(None, M), "b1": P(M),
                "w2": P(M, None), "b2": P(None),
                "ln_scale": P(None), "ln_bias": P(None),
            }
        layers.append(layer)
    return {"embeddings": emb, "layers": layers}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _layer_norm(x, scale, bias, eps, out_dtype=None):
    """Stats in f32; the result returns to ``out_dtype`` (the residual
    stream stays bf16 — at (B=1024, S=128, H=384) an f32 stream is 200 MB
    touched by every block, and HBM bandwidth, not MXU, bounds the pass)."""
    out_dtype = out_dtype or x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    normed = (xf - mu) * jax.lax.rsqrt(var + eps) * scale + bias
    return normed.astype(out_dtype)


def _dense_attention(q, k, v, mask):
    """q,k,v: (B, S, H, D); mask: (B, S) validity. Fused softmax-attention.

    Scores stay in the compute dtype (bf16): the (B, H, S, S) tensor is the
    pass's largest intermediate, and keeping it f32 doubles its HBM traffic
    for <5e-5 cosine deviation. Max-subtraction runs the exp in f32."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    bias = jnp.where(mask[:, None, None, :], 0.0, -1e9).astype(scores.dtype)
    scores = scores + bias
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp((scores - m).astype(jnp.float32)).astype(scores.dtype)
    probs = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def _attention_block(x, p, mask, config: EncoderConfig, attn_fn):
    cd = config.compute_dtype
    xc = x.astype(cd)
    B, S, H = x.shape
    q = (xc @ p["wq"].astype(cd) + p["bq"].astype(cd))
    k = (xc @ p["wk"].astype(cd) + p["bk"].astype(cd))
    v = (xc @ p["wv"].astype(cd) + p["bv"].astype(cd))
    shp = (B, S, config.heads, config.head_dim)
    out = attn_fn(q.reshape(shp), k.reshape(shp), v.reshape(shp), mask)
    out = out.reshape(B, S, H).astype(cd)
    out = out @ p["wo"].astype(cd) + p["bo"].astype(cd)
    return _layer_norm(xc + out, p["ln_scale"], p["ln_bias"],
                       config.layer_norm_eps, out_dtype=cd)


def _use_tanh_gelu(config: EncoderConfig) -> bool:
    if config.gelu == "auto":
        # tanh only where the approximation hides under the dtype's own
        # quantization noise: half-precision compute (bf16/f16). f32/f64
        # keep BERT's exact erf for checkpoint-golden parity.
        return jnp.dtype(config.compute_dtype).itemsize <= 2
    if config.gelu not in ("erf", "tanh"):
        raise ValueError(
            f"EncoderConfig.gelu must be 'auto', 'erf' or 'tanh'; "
            f"got {config.gelu!r}")
    return config.gelu == "tanh"


def _mlp_block(x, p, config: EncoderConfig):
    cd = config.compute_dtype
    xc = x.astype(cd)
    h = xc @ p["w1"].astype(cd) + p["b1"].astype(cd)
    h = jax.nn.gelu(h, approximate=_use_tanh_gelu(config))
    out = h @ p["w2"].astype(cd) + p["b2"].astype(cd)
    return _layer_norm(xc + out, p["ln_scale"], p["ln_bias"],
                       config.layer_norm_eps, out_dtype=cd)


def _moe_block(x, p, config: EncoderConfig):
    """Top-1 switch MoE: one-hot dispatch keeps everything a dense einsum
    (MXU-friendly; no dynamic shapes), experts sharded over the model axis.
    Every expert runs on every token, which a handful of experts in a BERT
    block can afford. Routed experts at scale (top-k of hundreds, a held
    range, a grouped product over the chosen pairs only) live in
    models/decoder.py ``moe_layer`` and ops/moe.py."""
    cd = config.compute_dtype
    E = config.num_experts
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)         # (B, S, E)
    top = jnp.argmax(gates, axis=-1)                # (B, S)
    onehot = jax.nn.one_hot(top, E, dtype=cd)       # (B, S, E)
    gate_val = jnp.sum(gates * onehot.astype(jnp.float32), axis=-1)
    # dispatch: every expert sees every token, masked by one-hot (dense form;
    # fine at encoder scale, avoids capacity/sort machinery)
    xc = x.astype(cd)
    h = jnp.einsum("bsh,ehi->bsei", xc, p["w1"].astype(cd))
    h = h + p["b1"].astype(cd)[None, None]
    if _use_tanh_gelu(config):
        h = jax.nn.gelu(h, approximate=True)
    else:
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=False).astype(cd)
    out = jnp.einsum("bsei,eih->bseh", h, p["w2"].astype(cd))
    out = out + p["b2"].astype(cd)[None, None]
    out = jnp.einsum("bseh,bse->bsh", out, onehot)
    out = (out.astype(jnp.float32) * gate_val[..., None]).astype(cd)
    return _layer_norm(x.astype(cd) + out, p["ln_scale"], p["ln_bias"],
                       config.layer_norm_eps, out_dtype=cd)


def _forward(params: dict, token_ids, mask, *, config: EncoderConfig,
             attn_fn: Callable, position_ids=None, token_type_ids=None):
    """Embedding + transformer stack → (B, S, H) final hidden states.
    ``position_ids=None`` keeps the standard 0..S-1 positions; the ragged
    path passes per-token positions so each packed document restarts at 0
    (byte-compatible with encoding it as its own row)."""
    emb = params["embeddings"]
    B, S = token_ids.shape
    cd = config.compute_dtype
    # Large batches: gather from a bf16 view of the table — the (V, H)
    # random-access read is the pass's most HBM-expensive op, and the one-off
    # f32→bf16 convert (~V*H*6 bytes) amortizes when the gather touches a
    # comparable volume. Small (serving) batches: gather f32 rows directly,
    # converting only what was read. B*S is static under jit, so this is a
    # trace-time branch, not device control flow.
    if B * S >= emb["token"].shape[0]:
        x = emb["token"].astype(cd)[token_ids]
    else:
        x = emb["token"][token_ids].astype(cd)
    if position_ids is None:
        x = x + emb["position"][:S][None].astype(cd)
    else:
        x = x + emb["position"][position_ids].astype(cd)
    if token_type_ids is None:
        x = x + emb["token_type"][0][None, None].astype(cd)
    else:
        x = x + emb["token_type"][token_type_ids].astype(cd)
    x = _layer_norm(x, emb["ln_scale"], emb["ln_bias"], config.layer_norm_eps,
                    out_dtype=cd)

    for layer in params["layers"]:
        x = _attention_block(x, layer["attn"], mask, config, attn_fn)
        if "moe" in layer:
            x = _moe_block(x, layer["moe"], config)
        else:
            x = _mlp_block(x, layer["mlp"], config)
    return x


def _normalized(pooled, config: EncoderConfig):
    if config.normalize:
        pooled = pooled / jnp.maximum(
            jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)
    return pooled


def encode(params: dict, token_ids, attention_mask, *,
           config: EncoderConfig,
           attn_fn: Callable | None = None,
           token_type_ids=None):
    """Forward pass → pooled, (optionally) L2-normalized embeddings.

    token_ids, attention_mask: (B, S) int32 / bool. ``attn_fn`` overrides the
    attention op (signature (q, k, v, mask) with (B,S,H,D) inputs) — pass a
    ring/Ulysses wrapper for sequence-parallel long-context encoding.
    """
    if attn_fn is None:
        attn_fn = _dense_attention
    mask = attention_mask.astype(bool)
    x = _forward(params, token_ids, mask, config=config, attn_fn=attn_fn,
                 token_type_ids=token_type_ids)
    if config.pooling == "cls":
        pooled = x[:, 0].astype(jnp.float32)
    else:  # mean over valid tokens
        xf = x.astype(jnp.float32)
        m = mask.astype(jnp.float32)[..., None]
        pooled = jnp.sum(xf * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
    return _normalized(pooled, config)


def _segment_attention(q, k, v, seg):
    """_dense_attention with a block-diagonal (same-segment) mask: token q
    attends token k iff they belong to the same packed document. Same
    softmax numerics as _dense_attention — only the bias mask differs."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    same = (seg[:, :, None] == seg[:, None, :]) & (seg >= 0)[:, None, :]
    bias = jnp.where(same[:, None, :, :], 0.0, -1e9).astype(scores.dtype)
    scores = scores + bias
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp((scores - m).astype(jnp.float32)).astype(scores.dtype)
    probs = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def encode_ragged(params: dict, token_ids, doc_map, position_ids,
                  doc_seq, doc_off, *, config: EncoderConfig):
    """Ragged-packed forward: variable-length documents packed back-to-back
    into fixed-width sequences (Ragged Paged Attention's batching applied
    to the encoder) → (n_docs, H) pooled embeddings.

    token_ids (B, W) int32: packed tokens, many docs per row;
    doc_map (B, W) int32: output row per token (-1 = padding) — doubles as
    the attention segment id, so docs sharing a sequence never attend each
    other; position_ids (B, W): positions restarting at 0 per doc;
    doc_seq/doc_off (N,): each output doc's (sequence, first-token offset),
    CLS pooling gathers there. Compilation depends only on (B, W, N) — the
    per-width bucket zoo collapses to a handful of sequence-count buckets.
    """
    mask = doc_map >= 0

    def attn(q, k, v, _mask):
        return _segment_attention(q, k, v, doc_map)

    x = _forward(params, token_ids, mask, config=config, attn_fn=attn,
                 position_ids=position_ids)
    n_docs = doc_seq.shape[0]
    if config.pooling == "cls":
        pooled = x[doc_seq, doc_off].astype(jnp.float32)
    else:  # per-document mean over the packed tokens
        B, W = token_ids.shape
        flat = x.reshape(B * W, -1).astype(jnp.float32)
        seg = jnp.where(mask, doc_map, n_docs).reshape(B * W)
        sums = jax.ops.segment_sum(flat, seg, num_segments=n_docs + 1)
        cnt = jax.ops.segment_sum(
            mask.astype(jnp.float32).reshape(B * W), seg,
            num_segments=n_docs + 1)
        pooled = sums[:n_docs] / jnp.maximum(cnt[:n_docs, None], 1.0)
    return _normalized(pooled, config)


@functools.partial(jax.jit, static_argnames=("config",))
def encode_jit(params, token_ids, attention_mask, *, config: EncoderConfig):
    return encode(params, token_ids, attention_mask, config=config)


def encoder_cost(config: EncoderConfig, batch: int, seq: int,
                 ragged: bool = False) -> tuple[float, float]:
    """Analytic (flops, bytes_moved) for one forward of ``batch x seq``
    tokens under ``config`` — the config-aware face of the shared cost
    model (engine/profiler.py owns the formulas; bench.py and the
    profiling hooks both resolve through them, so MFU numbers agree
    everywhere). ``ragged=True`` prices the packed segment-attention
    variant (encode_ragged), which additionally materializes the score
    tensor in HBM."""
    from pathway_tpu.engine.profiler import (encoder_cost as _cost,
                                             segment_attention_cost)

    fn = segment_attention_cost if ragged else _cost
    return fn(batch, seq, hidden=config.hidden,
              intermediate=config.intermediate, layers=config.layers)
