"""pathway_tpu.models — TPU-native model zoo for the LLM/RAG stack.

The reference runs its local models through torch
(SentenceTransformerEmbedder, xpacks/llm/embedders.py:268-326; HFPipelineChat,
xpacks/llm/llms.py:438). Here the flagship embedder is a pure-JAX
transformer encoder designed for the MXU: bfloat16 matmuls, static shapes,
mesh-sharded weights (tensor parallel), batch sharded over the data axis,
and optional ring/Ulysses attention for long sequences
(pathway_tpu/parallel/ring_attention.py). ``decoder.DecoderConfig`` is a
hybrid decoder backbone (gated delta-rule and attention layers, routed
experts) served the same way: prefill only, last-token pooling.
"""

from pathway_tpu.models.clip import (
    ClipConfig,
    clip_train_step,
    encode_image,
    encode_text,
    init_clip_params,
)
from pathway_tpu.models.decoder import DecoderConfig
from pathway_tpu.models.encoder import (
    EncoderConfig,
    encode,
    init_params,
    param_pspecs,
)
from pathway_tpu.models.tokenizer import HashTokenizer
from pathway_tpu.models.train import (
    contrastive_train_step,
    init_train_state,
    train_state_pspecs,
)

__all__ = [
    "ClipConfig",
    "DecoderConfig",
    "EncoderConfig",
    "clip_train_step",
    "encode",
    "encode_image",
    "encode_text",
    "init_clip_params",
    "init_params",
    "param_pspecs",
    "HashTokenizer",
    "contrastive_train_step",
    "init_train_state",
    "train_state_pspecs",
]
