"""The decoder of latent attention over a learned choice of keys (an indexer
that ranks every visible key for every query, its choice shared by the
layers behind it), a dense or an expert feed-forward a layer, a sigmoid
router and an ungated shared expert, as an embedder (``"model":
"glm_moe_dsa"``): how the program's embedder is built from a configuration,
what the reference is fed, and what one dispatch costs.

``build`` takes the reference's float32 weights **a layer at a time** (the
reference makes a layer when it is asked for: 3.3 GB at the published cut),
casts it to bfloat16 on the host and puts it on the device before the next
is made: 3.76 billion parameters are 7.52 GB there. What is kept float32 is
what the configuration's ``serving`` computes in float32 and is small: the
norms' weights, the router and its correction bias (6.3 MB a layer).

The cost functions are the benchmark's own arithmetic (nothing of the
program is imported for them), bfloat16 weights and activations assumed. A
range of the routed experts is held here, so under even routing a token
meets ``num_experts_per_tok * held / router outputs`` held experts; the
shared expert is every token's. Every held expert's weights are read once a
dispatch. What the indexers and the sparse cores have to do depends on how
the documents lie in a row, which a shape does not say:
:func:`indexer_cost` and :func:`sparse_attention_cost` take the pairs the
program's ``embedder.dispatch`` spans counted, :func:`dispatch_cost`, which
is handed the shape alone, states a length. **Both count the work the
layer's equations ask for, not what a lowering does**: a core that computes
every visible pair and masks, or one that gathers its chosen keys, is read
by the same yardstick.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: parameters the program keeps float32 on the device, by the last name of
#: their path (every 1-D array is kept so besides)
_FLOAT32 = ("router",)


def held_layers(config: dict) -> tuple[list, list]:
    """(``mlp_layer_types``, ``indexer_types``) of the layers held here: the
    published lists keep every entry, ``layers_held`` [lo, hi) are run."""
    lo, hi = config["layers_held"]
    return config["mlp_layer_types"][lo:hi], config["indexer_types"][lo:hi]


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
    mlp, indexers = held_layers(c)
    cfg = DecoderConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_hidden_layers=c["num_hidden_layers"],
        rms_norm_eps=c["rms_norm_eps"], zero_centred_norm=False,
        num_attention_heads=c["num_attention_heads"],
        rope_theta=float(c["rope_parameters"]["rope_theta"]),
        attention_method="MLA", q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        mla_scale_q_lora=False, mla_scale_kv_lora=False,
        ffn_hidden_size=c["intermediate_size"],
        mlp_layer_types=tuple(mlp), indexer_types=tuple(indexers),
        index_topk=c["index_topk"], index_n_heads=c["index_n_heads"],
        index_head_dim=c["index_head_dim"],
        first_k_dense_replace=c["first_k_dense_replace"],
        num_experts=c["published"]["n_routed_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        shared_expert_intermediate_size=None,
        n_shared_experts=c["n_shared_experts"],
        norm_topk_prob=c["norm_topk_prob"], scoring_func=c["scoring_func"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        hidden_act=c["hidden_act"], experts_held=tuple(c["experts_held"]),
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
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _latent_proj_params(c: dict) -> int:
    """One latent attention's five projections."""
    h, nh = c["hidden_size"], c["num_attention_heads"]
    key = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return h * c["q_lora_rank"] + c["q_lora_rank"] * nh * key \
        + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        + c["kv_lora_rank"] * nh * (c["qk_nope_head_dim"] + c["v_head_dim"]) \
        + nh * c["v_head_dim"] * h


def _indexer_proj_params(c: dict) -> int:
    """One indexer's three projections."""
    ni, di = c["index_n_heads"], c["index_head_dim"]
    return c["q_lora_rank"] * ni * di + c["hidden_size"] * (di + ni)


def _router_outputs(c: dict) -> int:
    return c["published"]["n_routed_experts"]


def _held(c: dict) -> int:
    lo, hi = c["experts_held"]
    return hi - lo


def held_a_token(c: dict) -> float:
    """Held experts a real token meets under even routing over all of the
    router's outputs."""
    return c["num_experts_per_tok"] * _held(c) / _router_outputs(c)


def sparse_attention_cost(config: dict, tokens: float, pairs_selected: float
                          ) -> tuple[float, float]:
    """(flops, bytes) of the sparse cores of dispatches that hold ``tokens``
    real tokens and ``pairs_selected`` chosen (query, key) pairs summed over
    the attention layers (the program's ``embedder.dispatch`` spans'
    ``attn_pairs_selected``: a query's visible keys or ``index_topk``, the
    fewer, a layer): a chosen pair costs a head ``qk_nope_head_dim +
    qk_rope_head_dim`` multiply-adds for its score and ``v_head_dim`` for
    its value, 2 x (256 + 256) flops; q, the expanded k and v are read and o
    is written once a layer, in bfloat16. Pairs a lowering computes and
    masks are no useful work."""
    c = config
    cores = c["num_hidden_layers"]
    key = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    nh, dv = c["num_attention_heads"], c["v_head_dim"]
    flops = 2.0 * (key + dv) * nh * pairs_selected
    nbytes = cores * tokens * 2.0 * nh * (2 * key + 2 * dv)
    return flops, nbytes


def indexer_cost(config: dict, tokens: float, pairs_indexed: float
                 ) -> tuple[float, float]:
    """(flops, bytes) of the indexers' scores and choices of dispatches that
    hold ``tokens`` real tokens and ``pairs_indexed`` visible (query, key)
    pairs summed over the layers with an indexer (the spans'
    ``attn_pairs_indexed``): a visible pair costs an index head
    ``index_head_dim`` multiply-adds, 2 x 128 flops over 32 heads; the index
    queries, the index key and the heads' weights of a token are read once
    an indexer (bfloat16, bfloat16, float32) and the choice is written once,
    a bit a visible pair. The projections that make them are
    :func:`dispatch_cost`'s."""
    c = config
    ni, di = c["index_n_heads"], c["index_head_dim"]
    indexers = held_layers(c)[1].count("full")
    flops = 2.0 * di * ni * pairs_indexed
    nbytes = indexers * tokens * (2.0 * ni * di + 2.0 * di + 4.0 * ni) \
        + pairs_indexed / 8.0
    return flops, nbytes


def experts_cost(config: dict, shape: tuple, fill: float = 1.0
                 ) -> tuple[float, float]:
    """(flops, bytes) of the held experts' grouped products of one dispatch
    of packed ``shape`` filled to the share ``fill`` (the program keeps
    padding out of the groups), all expert layers: the expected held experts
    a token times an expert's three matrices; every held expert's weights
    read once, a pair's input row read and output row written. A pair whose
    expert is held elsewhere costs nothing here; the shared expert is
    :func:`dispatch_cost`'s."""
    c = config
    layers = held_layers(c)[0].count("sparse")
    tokens = shape[0] * shape[1] * fill
    pairs = tokens * held_a_token(c)
    flops = 2.0 * pairs * _expert_params(c)
    nbytes = 2.0 * _held(c) * _expert_params(c) \
        + pairs * 2 * 2 * c["hidden_size"]
    return layers * flops, layers * nbytes


#: the document length :func:`dispatch_cost` counts attention at: the mix's
#: mean document (5,120 words and two marks), three to a row
STATED_DOCUMENT = 5122


def dispatch_cost(config: dict, shape: tuple, ragged: bool
                  ) -> tuple[float, float]:
    """(flops, bytes) of one forward of packed ``shape`` (rows, tokens a
    row): the latent attentions' projections, the indexers' projections,
    scores and choices, the sparse cores, the dense feed-forward, the
    routers, the shared and the held experts, every weight read once, the
    residual stream touched about four times in and out a sublayer, one
    embedding row a token. First-order, as the other architectures' are.
    **Every slot counts, padding included** (``encoder_roofline``'s reader
    hands the shape alone).

    **Attention is counted at documents of 5,122 tokens**, as many as fit a
    row whole, which the shape does not say: the mix's mean document. The
    visible pairs grow with the square of a document's length, so the mix's
    real rows hold more (its longer documents carry most of the tokens) and
    the share reads low, never over what the chip did."""
    c = config
    rows, width = shape
    tokens = rows * width
    h = c["hidden_size"]
    mlp, indexers = held_layers(c)
    layers, sparse = len(mlp), mlp.count("sparse")
    full = indexers.count("full")
    dense = layers * _latent_proj_params(c) + full * _indexer_proj_params(c) \
        + (layers - sparse) * 3 * h * c["intermediate_size"] \
        + sparse * (h * _router_outputs(c)
                    + c["n_shared_experts"] * _expert_params(c))
    n = min(STATED_DOCUMENT, width)
    docs = rows * (width // n)
    visible = docs * n * (n + 1) / 2
    topk = min(c["index_topk"], n)
    selected = docs * (topk * (topk + 1) / 2 + (n - topk) * topk)
    core_flops, core_bytes = sparse_attention_cost(c, tokens,
                                                   layers * selected)
    index_flops, index_bytes = indexer_cost(c, tokens, full * visible)
    expert_flops, expert_bytes = experts_cost(c, shape)
    flops = 2.0 * tokens * dense + core_flops + index_flops + expert_flops
    stream = 2 * tokens * h
    nbytes = 2.0 * dense + expert_bytes + core_bytes + index_bytes \
        + 8 * layers * stream + stream
    return flops, float(nbytes)
