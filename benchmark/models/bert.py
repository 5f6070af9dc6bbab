"""The BERT-family encoder (``"model": "bert"``): how the program's embedder
is built from a configuration, what the reference is fed, and what one
encoder dispatch costs.

The three callables every ``benchmark/models/<model>.py`` defines:

- ``build(config, weights) -> embedder``: the program's embedder object, as
  a user would construct it, holding ``weights``: the arrays the
  architecture's reference made from the seed
  (``benchmark/reference/<model>.py`` ``weights``), loaded in the type the
  program serves them in;
- ``tokens(embedder, config, texts) -> (ids, lengths)``: the reference's
  inputs, from the program's tokenizer;
- ``dispatch_cost(config, shape, ragged) -> (flops, bytes)``: the operations
  and bytes of one encoder dispatch of packed ``shape``, which
  ``encoder_roofline`` divides by.

The cost functions are copied from ``pathway_tpu/engine/profiler.py``
(``encoder_flops_per_token``, ``encoder_cost``, ``segment_attention_cost``)
so that no later PR to the program can move a roofline share by editing the
arithmetic; the originals are listed in PERF.md's open questions for
deletion.
"""

from __future__ import annotations

import inspect

import numpy as np


def build(config: dict, weights: dict):
    """The reference's float32 arrays put on the device in one call, as the
    tree the program's encoder takes (the reference names its arrays after
    it; float32 is the type the program holds them in, it computes in
    ``serving.compute_dtype``); the synthetic WordPiece vocab."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.models.tokenizer import (WordPieceTokenizer,
                                              make_synthetic_vocab)
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    c, serving = config, config["serving"]
    cfg = EncoderConfig(
        vocab_size=c["vocab_size"], hidden=c["hidden_size"],
        layers=c["num_hidden_layers"], heads=c["num_attention_heads"],
        intermediate=c["intermediate_size"],
        max_len=c["max_position_embeddings"],
        type_vocab_size=c["type_vocab_size"],
        layer_norm_eps=c["layer_norm_eps"], pooling=c["pooling"],
        normalize=c["normalize"],
        compute_dtype=getattr(jnp, serving["compute_dtype"]))
    params = jax.device_put(weights)
    tokenizer = WordPieceTokenizer(
        make_synthetic_vocab(
            [f"word{i}" for i in range(serving["vocab_words"])],
            vocab_size=cfg.vocab_size),
        max_len=serving["max_len"])
    if not tokenizer.uses_native:
        raise RuntimeError("the native WordPiece did not build; the "
                           "Python twin is not what a deployment runs")
    kwargs = {}
    # the packer is a constructor argument only while the constructor
    # takes it: once one path is the only one, the key is ignored
    if "ragged" in inspect.signature(
            JaxEncoderEmbedder.__init__).parameters:
        kwargs["ragged"] = bool(serving["ragged"])
    return JaxEncoderEmbedder(config=cfg, params=params, tokenizer=tokenizer,
                              max_len=serving["max_len"], **kwargs)


def tokens(embedder, config: dict, texts: list[str]
           ) -> tuple[np.ndarray, np.ndarray]:
    """(ids, lengths) of ``texts`` from the program's tokenizer, padded to
    the serving width."""
    width = config["serving"]["max_len"]
    ids, mask = embedder.tokenizer.batch([t or "." for t in texts],
                                         max_len=width)
    ids = np.pad(ids, ((0, 0), (0, width - ids.shape[1])))
    return ids.astype(np.int32), mask.sum(axis=1).astype(np.int32)


def dispatch_cost(config: dict, shape: tuple, ragged: bool
                  ) -> tuple[float, float]:
    """(flops, bytes) of one encoder dispatch of packed ``shape``
    (sequences, tokens a sequence)."""
    kw = dict(hidden=config["hidden_size"],
              intermediate=config["intermediate_size"],
              layers=config["num_hidden_layers"])
    if ragged:
        return segment_attention_cost(
            *shape, heads=config["num_attention_heads"], **kw)
    return encoder_cost(*shape, **kw)


def encoder_flops_per_token(hidden: int, intermediate: int, layers: int,
                            seq: int) -> float:
    """Forward FLOPs per token of the BERT-family encoder: 2 x the matmul
    parameters per token (QKV + out-proj 4*h*h, FFN up+down 2*h*f per
    layer) plus the attention score/value term (4*S*h per token per
    layer)."""
    per_layer = 2.0 * (4 * hidden * hidden + 2 * hidden * intermediate) \
        + 4.0 * seq * hidden
    return layers * per_layer


def encoder_cost(batch: int, seq: int, *, hidden: int, intermediate: int,
                 layers: int) -> tuple[float, float]:
    """(flops, bytes) of one dense forward of ``batch x seq`` tokens:
    every matmul parameter read once (bf16), the residual stream touched
    about four times in and four times out per block, one embedding row
    per token. First-order on purpose: the verdict needs the decade."""
    flops = batch * seq * encoder_flops_per_token(hidden, intermediate,
                                                  layers, seq)
    param_bytes = 2 * layers * (4 * hidden * hidden
                                + 2 * hidden * intermediate)
    stream = 2 * batch * seq * hidden
    return flops, float(param_bytes + 8 * layers * stream + stream)


def segment_attention_cost(batch: int, seq: int, *, hidden: int,
                           intermediate: int, layers: int,
                           heads: int) -> tuple[float, float]:
    """(flops, bytes) of one ragged-packed forward over ``batch`` packed
    sequences of ``seq`` tokens: the dense tree plus the (B, heads, S, S)
    bf16 score tensor written and read once per layer. (The program's copy
    leaves the head count out of that term.)"""
    flops, base = encoder_cost(batch, seq, hidden=hidden,
                               intermediate=intermediate, layers=layers)
    return flops, base + 2.0 * layers * 2 * batch * heads * seq * seq
