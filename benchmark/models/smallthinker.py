"""The decoder with window and full attention mixed and ReGLU routed experts,
as an embedder (``"model": "smallthinker"``): how the program's embedder is
built from a configuration, what the reference is fed, and what one dispatch
costs.

``build`` hands the reference's float32 arrays to the program **in
bfloat16**, cast on the host and put on the device in one call: 1.98 billion
parameters are 3.97 GB there. What is kept float32 is what the
configuration's ``serving`` computes in float32 and is small: the norms'
weights and the router (0.66 MB a layer). The host holds the float32 tree
(7.9 GB) and the bfloat16 one side by side for the length of the cast; the
float32 tree is the caller's and goes when ``build`` returns.

The cost functions are the benchmark's own arithmetic (nothing of the
program is imported for them), bfloat16 weights and activations assumed.
Every expert is held here, so a token meets its
``moe_num_active_primary_experts`` experts whatever the routing; every
expert's weights are read once a dispatch. Attention's work depends on how
the documents lie in a row, which a shape does not say:
:func:`attention_cost` takes the visible (query, key) pairs the program's
``embedder.dispatch`` spans counted, :func:`dispatch_cost`, which is handed
the shape alone, states a length.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: parameters the program keeps float32 on the device, by the last name of
#: their path (every 1-D array is kept so besides)
_FLOAT32 = ("router",)


def build(config: dict, weights: dict):
    """The program's embedder over ``weights`` (the reference's float32
    tree), as a user would construct it for this deployment."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.decoder import DecoderConfig
    from pathway_tpu.models.tokenizer import (WordPieceTokenizer,
                                              make_synthetic_vocab)
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    c, serving = config, config["serving"]
    cfg = DecoderConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_hidden_layers=c["num_hidden_layers"],
        rms_norm_eps=c["rms_norm_eps"], zero_centred_norm=False,
        num_attention_heads=c["num_attention_heads"],
        num_key_value_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        partial_rotary_factor=1.0, rope_theta=float(c["rope_theta"]),
        attention_gate=False, qk_norm=False,
        sliding_window_layout=tuple(c["sliding_window_layout"]),
        rope_layout=tuple(c["rope_layout"]),
        sliding_window_size=c["sliding_window_size"],
        num_experts=c["moe_num_primary_experts"],
        num_experts_per_tok=c["moe_num_active_primary_experts"],
        moe_intermediate_size=c["moe_ffn_hidden_size"],
        shared_expert_intermediate_size=None,
        norm_topk_prob=c["norm_topk_prob"], hidden_act="relu",
        router_input="mixer_input", max_len=serving["max_len"],
        pooling=c["pooling"], normalize=c["normalize"],
        compute_dtype=getattr(jnp, serving["compute_dtype"]))

    leaves, tree = jax.tree_util.tree_flatten_with_path(weights)

    def served(item):
        path, a = item
        keep = a.ndim < 2 or getattr(path[-1], "key", None) in _FLOAT32
        return a if keep else a.astype(jnp.bfloat16)

    with ThreadPoolExecutor(8) as pool:
        cast = list(pool.map(served, leaves))
    params = jax.device_put(jax.tree_util.tree_unflatten(tree, cast))
    del cast
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
    return 3 * c["hidden_size"] * c["moe_ffn_hidden_size"]


def _attention_proj_params(c: dict) -> int:
    h, hd = c["hidden_size"], c["head_dim"]
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    return h * nh * hd + 2 * h * nkv * hd + nh * hd * h


def _layer_kinds(c: dict) -> tuple[int, int]:
    """(full attention layers, window layers) of the held depth."""
    window = sum(c["sliding_window_layout"][:c["num_hidden_layers"]])
    return c["num_hidden_layers"] - window, window


def attention_cost(config: dict, tokens: float, pairs_full: float,
                   pairs_window: float) -> tuple[float, float]:
    """(flops, bytes) of the attention cores of dispatches that hold
    ``tokens`` real tokens, ``pairs_full`` visible (query, key) pairs in a
    full layer and ``pairs_window`` in a window layer (the program's
    ``embedder.dispatch`` spans count them: a document of n tokens has
    n (n + 1) / 2, cut at the window), all layers: a visible pair costs a
    query head ``head_dim`` multiply-adds for its score and as many for its
    value, 4 x 128 flops; q, k and v are read and o is written once, in
    bfloat16. Blocks the kernel skips are not counted, and what it
    computes of a block beyond the visible pairs is no useful work."""
    c = config
    n_full, n_window = _layer_kinds(c)
    nh, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    flops = 4.0 * hd * nh * (n_full * pairs_full + n_window * pairs_window)
    nbytes = (n_full + n_window) * tokens * 2.0 * hd * (2 * nh + 2 * nkv)
    return flops, nbytes


def experts_cost(config: dict, shape: tuple, fill: float = 1.0
                 ) -> tuple[float, float]:
    """(flops, bytes) of the routed experts' grouped products of one
    dispatch of packed ``shape`` filled to the share ``fill`` (the program
    keeps padding out of the groups), all layers: a token's six experts
    times an expert's three matrices; every expert's weights read once, a
    pair's input row read and output row written."""
    c = config
    tokens = shape[0] * shape[1] * fill
    pairs = tokens * c["moe_num_active_primary_experts"]
    flops = 2.0 * pairs * _expert_params(c)
    nbytes = 2.0 * c["moe_num_primary_experts"] * _expert_params(c) \
        + pairs * 2 * 2 * c["hidden_size"]
    return c["num_hidden_layers"] * flops, c["num_hidden_layers"] * nbytes


#: the document length :func:`dispatch_cost` counts attention at
STATED_DOCUMENT = "sliding_window_size"


def dispatch_cost(config: dict, shape: tuple, ragged: bool
                  ) -> tuple[float, float]:
    """(flops, bytes) of one forward of packed ``shape`` (rows, tokens a
    row): attention's projections, its cores, the router, the routed
    experts, every weight read once, the residual stream touched about four
    times in and out a layer, one embedding row a token. First-order, as the
    other two are. **Every slot counts, padding included**
    (``encoder_roofline``'s reader hands the shape alone).

    **Attention is counted at documents of ``sliding_window_size`` tokens**
    (4,096: four to a row of 16,384), which the shape does not say: the
    mix's mean is 3,975, so it is the work of an average row; a window layer
    and a full layer then cost alike (the window cuts nothing), and since
    the pairs grow with the square of a document's length the mix's real
    rows hold more (its longer documents carry 72 % of the tokens), so the
    share reads low, never over what the chip did."""
    c = config
    rows, width = shape
    tokens = rows * width
    layers = c["num_hidden_layers"]
    dense = layers * (_attention_proj_params(c)
                      + c["hidden_size"] * c["moe_num_primary_experts"])
    n = min(c[STATED_DOCUMENT], width)
    pairs = rows * (width // n) * n * (n + 1) / 2
    attention_flops, attention_bytes = attention_cost(c, tokens, pairs, pairs)
    expert_flops, expert_bytes = experts_cost(c, shape)
    flops = 2.0 * tokens * dense + attention_flops + expert_flops
    stream = 2 * tokens * c["hidden_size"]
    nbytes = 2.0 * dense + expert_bytes + attention_bytes \
        + 8 * layers * stream + stream
    return flops, float(nbytes)
