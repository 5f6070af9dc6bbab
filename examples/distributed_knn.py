"""Distributed streaming KNN template (BASELINE config 5: multi-worker
distributed KNN over a message stream, pod-scale shard over ICI).

A live stream of documents (Kafka when configured, otherwise a watched
directory standing in for the topic) is embedded and added to a KNN index
whose slab is SHARDED OVER THE DEVICE MESH: with N chips visible, each
holds 1/N of the vectors in HBM and queries fan out over ICI with a
per-shard top-k merge (parallel/sharded_knn.py — the TPU-native
counterpart of the reference's per-worker index instances,
src/external_integration/mod.rs:46). On one chip it degrades to the
single-slab index; the sharding is exercised chipless via the 8-device
virtual CPU mesh (tests/test_parallel.py, dryrun_multichip).

Run:
    python examples/distributed_knn.py ./docs --port 8080
    # or against Kafka:
    python examples/distributed_knn.py --kafka localhost:9092 --topic docs
then:
    curl -X POST localhost:8080/v1/retrieve -d '{"query": "ring attention"}'
"""

from __future__ import annotations

import argparse
import hashlib
import logging

import numpy as np

import pathway_tpu as pw
from pathway_tpu.models.hf_loader import find_local_checkpoint
from pathway_tpu.stdlib.indexing import default_brute_force_knn_document_index
from pathway_tpu.io.http import PathwayWebserver, rest_connector


MODEL = "BAAI/bge-small-en-v1.5"


def make_embedder(dim_holder: dict, force_hash: bool = False):
    """The serving embedder; ``force_hash`` is for graph-only collection
    (``__pathway_check__``), which must build no model."""
    if not force_hash:
        from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

        if find_local_checkpoint(MODEL):
            emb = JaxEncoderEmbedder(model=MODEL)
        else:
            logging.getLogger("distributed_knn").warning(
                "no %s checkpoint in the local HF cache: embedding with "
                "seeded random weights at the BGE-small shape — ranking "
                "is structural only", MODEL)
            emb = JaxEncoderEmbedder()
        dim_holder["dim"] = emb.get_embedding_dimension()
        return emb

    dim_holder["dim"] = 64

    @pw.udf(deterministic=True)
    def hash_embed(text: str) -> np.ndarray:
        v = np.zeros(64)
        for tok in str(text).lower().split():
            h = int(hashlib.md5(tok.encode()).hexdigest(), 16)
            v[h % 64] += 1.0
        n = np.linalg.norm(v)
        return v / n if n else v

    return hash_embed


def build(*, docs_dir: str | None = None, kafka: str | None = None,
          topic: str = "docs", host: str = "127.0.0.1", port: int = 8080,
          force_hash_embedder: bool = False) -> None:
    """Construct the sharded-KNN serving graph (no execution)."""
    if kafka:
        docs = pw.io.kafka.read(
            {"bootstrap.servers": kafka, "group.id": "pw-knn"},
            topic=topic, format="plaintext")
    else:
        docs = pw.io.fs.read(docs_dir, format="plaintext_by_file",
                             mode="streaming")

    holder: dict = {}
    embedder = make_embedder(holder, force_hash=force_hash_embedder)
    # mesh='auto': >1 device on the data axis -> slab sharded over ICI
    # with per-shard top-k merge; 1 device -> plain HBM slab. bf16 halves
    # per-chip slab bytes/scan time; dtype="int8" halves them again
    # (~30M vectors/chip at 384 dims)
    index = default_brute_force_knn_document_index(
        docs.data, docs, dimensions=holder["dim"], embedder=embedder,
        mesh="auto", dtype="bfloat16")

    class QuerySchema(pw.Schema):
        query: str
        k: int = 3

    ws = PathwayWebserver(host=host, port=port)
    queries, writer = rest_connector(
        webserver=ws, route="/v1/retrieve", schema=QuerySchema,
        delete_completed_queries=True)
    hits = index.query_as_of_now(queries.query, number_of_matches=queries.k)
    results = queries.select(
        result=pw.apply(lambda t: list(t or ()),
                        hits.restrict(queries).data))
    writer(results)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("docs", nargs="?", help="directory standing in for the "
                    "stream when --kafka is not given")
    ap.add_argument("--kafka", help="bootstrap servers, e.g. localhost:9092")
    ap.add_argument("--topic", default="docs")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--host", default="127.0.0.1")
    args = ap.parse_args()

    if not args.kafka and not args.docs:
        ap.error("pass a docs directory or --kafka")
    build(docs_dir=args.docs, kafka=args.kafka, topic=args.topic,
          host=args.host, port=args.port)
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)


if __name__ == "__main__":
    main()
elif __name__ == "__pathway_check__":
    # graph-only import by `python -m pathway_tpu check`; the hash
    # embedder keeps collection model-free even when checkpoints exist
    build(docs_dir="./docs", force_hash_embedder=True)
