"""Adaptive-RAG serving template (reference:
python/pathway/xpacks/llm/question_answering.py:478 AdaptiveRAGQuestionAnswerer
+ templates). Live document indexing + REST question answering with geometric
document-count escalation.

Run:
    python examples/adaptive_rag.py ./docs --port 8080
then:
    curl -X POST localhost:8080/v1/pw_ai_answer \
         -d '{"prompt": "what is a quokka?"}'

Embeds on the device with JaxEncoderEmbedder either way: the local BGE
checkpoint when the HF cache holds one, otherwise seeded random weights at
the BGE-small shape (same kernels, same device traffic; ranking is then
structural only, and one log line says so).
"""

from __future__ import annotations

import argparse
import hashlib
import logging

import numpy as np

import pathway_tpu as pw
from pathway_tpu.models.hf_loader import find_local_checkpoint
from pathway_tpu.xpacks.llm.question_answering import (
    AdaptiveRAGQuestionAnswerer)
from pathway_tpu.xpacks.llm.splitters import TokenCountSplitter
from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer


MODEL = "BAAI/bge-small-en-v1.5"


def make_embedder(force_hash: bool = False):
    """The serving embedder; ``force_hash`` is for graph-only collection
    (``__pathway_check__``), which must build no model."""
    if not force_hash:
        from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

        if find_local_checkpoint(MODEL):
            return JaxEncoderEmbedder(model=MODEL)
        logging.getLogger("adaptive_rag").warning(
            "no %s checkpoint in the local HF cache: embedding with seeded "
            "random weights at the BGE-small shape — ranking is structural "
            "only", MODEL)
        return JaxEncoderEmbedder()

    @pw.udf(deterministic=True)
    def hash_embed(text: str) -> np.ndarray:
        v = np.zeros(64)
        for tok in text.lower().split():
            h = int(hashlib.md5(tok.encode()).hexdigest(), 16)
            v[h % 64] += 1.0
        n = np.linalg.norm(v)
        return v / n if n else v

    return hash_embed


class EchoChat(pw.udfs.UDF):
    """Offline stand-in for an LLM chat: echoes the top context line.
    Swap for pw.xpacks.llm.llms.OpenAIChat(...) with credentials."""

    def __wrapped__(self, messages, **kwargs) -> str:
        if isinstance(messages, list):  # chat-messages form
            text = "\n".join(str(m.get("content", m)) if isinstance(m, dict)
                             else str(m) for m in messages)
        else:
            text = str(messages)
        lines = [l.strip() for l in text.splitlines() if l.strip()]
        docs, in_docs = [], False
        for l in lines:
            low = l.lower()
            if low.startswith("documents"):
                in_docs = True
                continue
            if low.startswith(("question", "answer")):
                in_docs = False
                continue
            if in_docs and not l.startswith("[doc"):
                docs.append(l)
        if not docs:
            return "No information found"
        return f"[context] {max(docs, key=len)[:200]}"


def build(docs_dir: str, *, port: int = 8080,
          force_hash_embedder: bool = False):
    """Construct the adaptive-RAG serving graph; returns the answerer
    (its graph is fully built — only run_server() executes anything)."""
    docs = pw.io.fs.read(docs_dir, format="plaintext_by_file",
                         mode="streaming", with_metadata=True)
    store = VectorStoreServer(
        docs, embedder=make_embedder(force_hash=force_hash_embedder),
        splitter=TokenCountSplitter(max_tokens=120))
    answerer = AdaptiveRAGQuestionAnswerer(
        llm=EchoChat(), indexer=store, n_starting_documents=2, factor=2,
        max_iterations=3)
    answerer.build_server(host="0.0.0.0", port=port)
    return answerer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("docs_dir")
    ap.add_argument("--port", type=int, default=8080)
    args = ap.parse_args()

    answerer = build(args.docs_dir, port=args.port)
    answerer.run_server()


if __name__ == "__main__":
    main()
elif __name__ == "__pathway_check__":
    # graph-only import by `python -m pathway_tpu check`; the hash
    # embedder keeps collection model-free even when checkpoints exist
    build("./docs", force_hash_embedder=True)
