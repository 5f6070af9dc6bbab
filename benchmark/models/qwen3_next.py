"""The hybrid DeltaNet/attention decoder with routed experts, as an embedder
(``"model": "qwen3_next"``): how the program's embedder is built from a
configuration, what the reference is fed, and what one dispatch costs.

``build`` hands the reference's float32 arrays to the program **in
bfloat16**, cast on the host and put on the device in one call: 3.52 billion
parameters are 7.04 GB there, not 14. What is kept float32 is what the
configuration's ``serving`` computes in float32 and is small: the norms'
weights, the DeltaNet's ``A_log`` and ``dt_bias``, the router and the shared
expert's gate (4.2 MB a layer). The host holds the float32 tree (14.1 GB)
and the bfloat16 one (7.0 GB) side by side for the length of the cast; the
float32 tree is the caller's and goes when ``build`` returns.

The cost functions are the benchmark's own arithmetic (nothing of the
program is imported for them), bfloat16 weights and activations assumed. A
token meets ``num_experts_per_tok * held / routed`` held experts in
expectation, which even routing gives and seeded random weights give
nearly (``moe.expert_load_max_over_mean`` says how nearly); every held
expert's weights are read once a dispatch, however few tokens it took.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: parameters the program keeps float32 on the device, by the last name of
#: their path (every 1-D array is kept so besides)
_FLOAT32 = ("router", "shared_router")


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
        full_attention_interval=c["full_attention_interval"],
        rms_norm_eps=c["rms_norm_eps"],
        num_attention_heads=c["num_attention_heads"],
        num_key_value_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        partial_rotary_factor=c["partial_rotary_factor"],
        rope_theta=float(c["rope_theta"]),
        linear_num_key_heads=c["linear_num_key_heads"],
        linear_num_value_heads=c["linear_num_value_heads"],
        linear_key_head_dim=c["linear_key_head_dim"],
        linear_value_head_dim=c["linear_value_head_dim"],
        linear_conv_kernel_dim=c["linear_conv_kernel_dim"],
        num_experts=c["num_experts_routed"],
        num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        shared_expert_intermediate_size=c["shared_expert_intermediate_size"],
        norm_topk_prob=c["norm_topk_prob"],
        experts_held=tuple(c["experts_held"]), max_len=serving["max_len"],
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
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _held(c: dict) -> int:
    lo, hi = c["experts_held"]
    return hi - lo


def _held_a_token(c: dict) -> float:
    return c["num_experts_per_tok"] * _held(c) / c["num_experts_routed"]


def _deltanet_proj_params(c: dict) -> int:
    kd = c["linear_num_key_heads"] * c["linear_key_head_dim"]
    vd = c["linear_num_value_heads"] * c["linear_value_head_dim"]
    h = c["hidden_size"]
    return h * (2 * kd + 2 * vd) + h * 2 * c["linear_num_value_heads"] \
        + c["linear_conv_kernel_dim"] * (2 * kd + vd) + vd * h


def _attention_proj_params(c: dict) -> int:
    h, hd = c["hidden_size"], c["head_dim"]
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    return h * nh * 2 * hd + 2 * h * nkv * hd + nh * hd * h


def _feed_forward_dense_params(c: dict) -> int:
    """Router, shared expert and its gate: what every token meets."""
    h = c["hidden_size"]
    return h * c["num_experts_routed"] \
        + 3 * h * c["shared_expert_intermediate_size"] + h


def _layer_kinds(c: dict) -> tuple[int, int]:
    """(DeltaNet layers, attention layers) of the held depth."""
    n = c["num_hidden_layers"]
    attention = n // c["full_attention_interval"]
    return n - attention, attention


SCAN_CHUNK = 64


def scan_cost(config: dict, shape: tuple, fill: float = 1.0
              ) -> tuple[float, float]:
    """(flops, bytes) of the chunked delta-rule scans of one dispatch of
    packed ``shape`` (rows, tokens a row) whose slots hold real tokens to
    the share ``fill`` (padding is no useful work: the kernel's own
    roofline passes the share the ``embedder.dispatch`` spans give, the
    whole forward's ``dispatch_cost`` counts every slot), all DeltaNet
    layers: a value
    head's token costs, in multiply-adds, the chunk's two score products
    (2 C dk), its triangular solve (C (dk + dv) / 2), three products with
    the state (3 dk dv) and the scores' with the corrected values (C dv).
    Bytes: q and k of the key heads, v in and o out of the value heads in
    bfloat16, and the float32 state read and written once a chunk."""
    c = config
    tokens = shape[0] * shape[1] * fill
    nk, nv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    macs = nv * (2 * SCAN_CHUNK * dk + SCAN_CHUNK * (dk + dv) / 2
                 + 3 * dk * dv + SCAN_CHUNK * dv)
    nbytes = 2 * (2 * nk * dk + 2 * nv * dv) \
        + nv * 2 * 4 * dk * dv / SCAN_CHUNK
    layers, _ = _layer_kinds(c)
    return layers * tokens * 2.0 * macs, layers * tokens * float(nbytes)


def experts_cost(config: dict, shape: tuple, fill: float = 1.0
                 ) -> tuple[float, float]:
    """(flops, bytes) of the routed experts' grouped products of one
    dispatch of packed ``shape`` filled to the share ``fill`` (the program
    keeps padding out of the groups), all layers: the expected held experts
    a token times an expert's three matrices; every held expert's weights
    read once, a pair's input row read and output row written."""
    c = config
    tokens = shape[0] * shape[1] * fill
    pairs = tokens * _held_a_token(c)
    flops = 2.0 * pairs * _expert_params(c)
    nbytes = 2.0 * _held(c) * _expert_params(c) \
        + pairs * 2 * 2 * c["hidden_size"]
    return c["num_hidden_layers"] * flops, c["num_hidden_layers"] * nbytes


def dispatch_cost(config: dict, shape: tuple, ragged: bool
                  ) -> tuple[float, float]:
    """(flops, bytes) of one forward of packed ``shape`` (rows, tokens a
    row): the mixers' projections, the scans, attention's two products over
    the causal half of a row and its score tensor written and read, the
    router and the shared expert, the routed experts, every weight read
    once, the residual stream touched about four times in and out a layer,
    one embedding row a token. First-order, as the BERT cost is. **Every
    slot counts, padding included** (``encoder_roofline``'s reader hands the
    shape alone): where the packer fills 61 % of a dispatch the share reads
    as much as a fifth higher than the useful work's, so it is not to be held
    beside ``bge-small-10m``'s, whose rows are nearly full."""
    c = config
    rows, width = shape
    tokens = rows * width
    n_delta, n_attn = _layer_kinds(c)
    layers = c["num_hidden_layers"]
    dense = n_delta * _deltanet_proj_params(c) \
        + n_attn * _attention_proj_params(c) \
        + layers * _feed_forward_dense_params(c)
    scores = n_attn * 2 * c["num_attention_heads"] * c["head_dim"] \
        * (width + 1) / 2
    scan_flops, scan_bytes = scan_cost(c, shape)
    expert_flops, expert_bytes = experts_cost(c, shape)
    flops = 2.0 * tokens * (dense + scores) + scan_flops + expert_flops
    stream = 2 * tokens * c["hidden_size"]
    score_tensor = n_attn * 2 * 2 * rows * c["num_attention_heads"] \
        * width * width
    nbytes = 2.0 * dense + expert_bytes + scan_bytes + score_tensor \
        + 8 * layers * stream + stream
    return flops, float(nbytes)
