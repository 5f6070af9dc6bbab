"""The decoder of two latent attention sublayers, two dense feed-forwards and
a shortcut-connected expert layer with identity experts a layer, as an
embedder (``"model": "longcat_flash"``): how the program's embedder is built
from a configuration, what the reference is fed, and what one dispatch
costs.

``build`` takes the reference's float32 weights **a layer at a time** (the
reference makes a layer when it is asked for: 5 GB at the published cut),
casts it to bfloat16 on the host and puts it on the device before the next
is made: 5.07 billion parameters are 10.14 GB there, and the host never
holds more than one layer in float32 beside its bfloat16 copy. What is kept
float32 is what the configuration's ``serving`` computes in float32 and is
small: the norms' weights, the router and its correction bias (19 MB a
layer).

The cost functions are the benchmark's own arithmetic (nothing of the
program is imported for them), bfloat16 weights and activations assumed. A
range of the experts with weights is held here, so under even routing a
token meets ``moe_topk * held / router outputs`` held experts; the identity
experts' pairs cost no product. Every held expert's weights are read once a
dispatch. Attention's work depends on how the documents lie in a row, which
a shape does not say: :func:`attention_cost` takes the visible (query, key)
pairs the program's ``embedder.dispatch`` spans counted,
:func:`dispatch_cost`, which is handed the shape alone, states a length.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: parameters the program keeps float32 on the device, by the last name of
#: their path (every 1-D array is kept so besides)
_FLOAT32 = ("router",)
#: a layer's attention sublayers, and its dense feed-forwards
SUBLAYERS = 2


def build(config: dict, weights: dict):
    """The program's embedder over ``weights`` (the reference's float32
    tree, whose ``"layers"`` are made one at a time), as a user would
    construct it for this deployment."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.decoder import DecoderConfig
    from pathway_tpu.models.tokenizer import (WordPieceTokenizer,
                                              make_synthetic_vocab)
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    c, serving = config, config["serving"]
    cfg = DecoderConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_hidden_layers=c["num_layers"], rms_norm_eps=c["rms_norm_eps"],
        zero_centred_norm=False,
        num_attention_heads=c["num_attention_heads"],
        rope_theta=float(c["rope_theta"]),
        attention_method=c["attention_method"],
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        mla_scale_q_lora=c["mla_scale_q_lora"],
        mla_scale_kv_lora=c["mla_scale_kv_lora"],
        ffn_hidden_size=c["ffn_hidden_size"],
        num_experts=c["published"]["n_routed_experts"],
        num_experts_per_tok=c["moe_topk"],
        moe_intermediate_size=c["expert_ffn_hidden_size"],
        shared_expert_intermediate_size=None, norm_topk_prob=False,
        zero_expert_num=c["zero_expert_num"],
        zero_expert_type=c["zero_expert_type"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        hidden_act="silu", experts_held=tuple(c["experts_held"]),
        max_len=serving["max_len"], pooling=c["pooling"],
        normalize=c["normalize"],
        compute_dtype=getattr(jnp, serving["compute_dtype"]))

    def served(item):
        path, a = item
        keep = a.ndim < 2 or getattr(path[-1], "key", None) in _FLOAT32
        return a if keep else a.astype(jnp.bfloat16)

    def on_device(tree):
        """``tree`` in the dtypes it is served in, on the device; the
        float32 arrays are the caller's to drop."""
        leaves, shape = jax.tree_util.tree_flatten_with_path(tree)
        with ThreadPoolExecutor(8) as pool:
            cast = list(pool.map(served, leaves))
        return jax.block_until_ready(jax.device_put(
            jax.tree_util.tree_unflatten(shape, cast)))

    params = on_device({"embed": weights["embed"],
                        "final_norm": weights["final_norm"]})
    # a layer is made, cast, shipped and dropped before the next is made
    layers = weights["layers"]
    params["layers"] = [on_device(layers[i]) for i in range(len(layers))]
    tokenizer = WordPieceTokenizer(
        make_synthetic_vocab(
            [f"word{i}" for i in range(serving["vocab_words"])],
            vocab_size=cfg.vocab_size),
        max_len=serving["max_len"])
    if not tokenizer.uses_native:
        raise RuntimeError("the native WordPiece did not build; the "
                           "Python twin is not what a deployment runs")
    return JaxEncoderEmbedder(
        config=cfg, params=params, tokenizer=tokenizer,
        max_len=serving["max_len"], ragged=bool(serving["ragged"]),
        ragged_max_seqs=serving["rows_per_dispatch"])


def tokens(embedder, config: dict, texts: list[str]
           ) -> tuple[np.ndarray, np.ndarray]:
    """(ids, lengths) of ``texts`` from the program's tokenizer, padded to
    the serving width."""
    width = config["serving"]["max_len"]
    ids, mask = embedder.tokenizer.batch([t or "." for t in texts],
                                         max_len=width)
    ids = np.pad(ids, ((0, 0), (0, width - ids.shape[1])))
    return ids.astype(np.int32), mask.sum(axis=1).astype(np.int32)


# -- what a dispatch costs ------------------------------------------------------
# Multiply-adds a token, from the configuration's keys; a flop is half of one.

def _expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["expert_ffn_hidden_size"]


def _latent_proj_params(c: dict) -> int:
    """One latent attention sublayer's five projections."""
    h, nh = c["hidden_size"], c["num_attention_heads"]
    key = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return h * c["q_lora_rank"] + c["q_lora_rank"] * nh * key \
        + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        + c["kv_lora_rank"] * nh * (c["qk_nope_head_dim"] + c["v_head_dim"]) \
        + nh * c["v_head_dim"] * h


def _router_outputs(c: dict) -> int:
    return c["published"]["n_routed_experts"] + c["zero_expert_num"]


def _held(c: dict) -> int:
    lo, hi = c["experts_held"]
    return hi - lo


def held_a_token(c: dict) -> float:
    """Held experts a real token meets under even routing over all of the
    router's outputs."""
    return c["moe_topk"] * _held(c) / _router_outputs(c)


def attention_cost(config: dict, tokens: float, pairs_full: float,
                   pairs_window: float = 0.0) -> tuple[float, float]:
    """(flops, bytes) of the latent attention cores of dispatches that hold
    ``tokens`` real tokens and ``pairs_full`` visible (query, key) pairs in
    one sublayer (the program's ``embedder.dispatch`` spans count them: a
    document of n tokens has n (n + 1) / 2; the model has no window, and
    ``pairs_window`` is not read), all sublayers: a visible pair costs a
    head ``qk_nope_head_dim + qk_rope_head_dim`` multiply-adds for its score
    and ``v_head_dim`` for its value, 2 x (192 + 128) flops; q, the expanded
    k and v are read and o is written once, in bfloat16. The zeros the
    kernel pads the rotary part with, the blocks it skips and what it
    computes of a block beyond the visible pairs are no useful work."""
    c = config
    cores = SUBLAYERS * c["num_layers"]
    key = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    nh, dv = c["num_attention_heads"], c["v_head_dim"]
    flops = 2.0 * (key + dv) * nh * cores * pairs_full
    nbytes = cores * tokens * 2.0 * nh * (2 * key + 2 * dv)
    return flops, nbytes


def experts_cost(config: dict, shape: tuple, fill: float = 1.0
                 ) -> tuple[float, float]:
    """(flops, bytes) of the held experts' grouped products of one dispatch
    of packed ``shape`` filled to the share ``fill`` (the program keeps
    padding out of the groups), all layers: the expected held experts a
    token times an expert's three matrices; every held expert's weights
    read once, a pair's input row read and output row written. A pair that
    chose an identity expert, or one held elsewhere, costs nothing here."""
    c = config
    tokens = shape[0] * shape[1] * fill
    pairs = tokens * held_a_token(c)
    flops = 2.0 * pairs * _expert_params(c)
    nbytes = 2.0 * _held(c) * _expert_params(c) \
        + pairs * 2 * 2 * c["hidden_size"]
    return c["num_layers"] * flops, c["num_layers"] * nbytes


#: the document length :func:`dispatch_cost` counts attention at: the mix's
#: mean section (3,328 words and two marks), two to a row and a fifth empty
STATED_DOCUMENT = 3330


def dispatch_cost(config: dict, shape: tuple, ragged: bool
                  ) -> tuple[float, float]:
    """(flops, bytes) of one forward of packed ``shape`` (rows, tokens a
    row): the latent attention sublayers' projections and cores, the dense
    feed-forwards, the router, the held experts, every weight read once, the
    residual stream touched about four times in and out a sublayer, one
    embedding row a token. First-order, as the other architectures' are.
    **Every slot counts, padding included** (``encoder_roofline``'s reader
    hands the shape alone).

    **Attention is counted at documents of 3,330 tokens**, as many as fit a
    row whole, which the shape does not say: the mix's mean section. The
    pairs grow with the square of a section's length, so the mix's real rows
    hold more (its longer sections carry most of the tokens) and the share
    reads low, never over what the chip did."""
    c = config
    rows, width = shape
    tokens = rows * width
    layers, h = c["num_layers"], c["hidden_size"]
    dense = layers * (SUBLAYERS * (_latent_proj_params(c)
                                   + 3 * h * c["ffn_hidden_size"])
                      + h * _router_outputs(c))
    n = min(STATED_DOCUMENT, width)
    pairs = rows * (width // n) * n * (n + 1) / 2
    attention_flops, attention_bytes = attention_cost(c, tokens, pairs)
    expert_flops, expert_bytes = experts_cost(c, shape)
    flops = 2.0 * tokens * dense + attention_flops + expert_flops
    stream = 2 * tokens * h
    nbytes = 2.0 * dense + expert_bytes + attention_bytes \
        + 8 * SUBLAYERS * layers * stream + stream
    return flops, float(nbytes)
