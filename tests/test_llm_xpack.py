"""LLM xpack tests — fake models injected like the reference test suite
(xpacks/llm/tests/test_vector_store.py:107-121)."""

import asyncio

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.debug import table_from_rows, table_to_pandas
from pathway_tpu.internals import schema as sch
from pathway_tpu.internals.json import Json
from pathway_tpu.xpacks.llm import llms, prompts, rerankers, splitters
from pathway_tpu.xpacks.llm.question_answering import (
    AdaptiveRAGQuestionAnswerer,
    BaseRAGQuestionAnswerer,
    answer_with_geometric_rag_strategy,
)
from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer


@pw.udf
def fake_embedder(text: str) -> np.ndarray:
    """Deterministic bag-of-words embedding (dimension 16). Uses md5, not
    hash(): str hashing is PYTHONHASHSEED-randomized and unlucky seeds
    collide enough to flip nearest-neighbour ranks (seed 6 did)."""
    import hashlib

    vec = np.zeros(16)
    for w in str(text).lower().split():
        h = int(hashlib.md5(w.encode()).hexdigest(), 16)
        vec[h % 16] += 1.0
    n = np.linalg.norm(vec)
    return vec / n if n else vec


class FakeChat(llms.BaseChat):
    """Echoes doc 1's text when there is context, else the no-info answer."""

    def __init__(self, min_docs: int = 1):
        super().__init__()
        self.min_docs = min_docs
        self.calls = []

    async def __wrapped__(self, messages, **kwargs):
        prompt = self._as_messages(messages)[-1]["content"]
        n_docs = prompt.count("[doc ")
        self.calls.append(n_docs)
        if n_docs >= self.min_docs:
            return f"answer from {n_docs} docs"
        return prompts.NO_INFO_ANSWER


def _docs_table():
    schema = sch.schema_from_types(data=str, _metadata=pw.Json)
    rows = [
        ("the quick brown fox jumps over the lazy dog",
         Json({"path": "/a.txt", "modified_at": 100})),
        ("TPU systolic arrays multiply matrices fast",
         Json({"path": "/b.txt", "modified_at": 200})),
        ("ring attention rotates blocks around the interconnect",
         Json({"path": "/c.txt", "modified_at": 300})),
    ]
    return table_from_rows(schema, rows)


def _result_rows(table):
    df = table_to_pandas(table, include_id=False)
    return df.to_dict("records")


def test_vector_store_retrieve_batch():
    store = VectorStoreServer(_docs_table(), embedder=fake_embedder)
    schema = sch.schema_from_types(query=str, k=int,
                                   metadata_filter=type(None),
                                   filepath_globpattern=type(None))
    queries = table_from_rows(
        schema, [("systolic arrays multiply", 2, None, None)])
    res = store.retrieve_query(queries)
    rows = _result_rows(res.select(result=pw.this.result))
    pw.run()
    matches = rows[0]["result"].value
    assert len(matches) == 2
    assert "systolic" in matches[0]["text"]
    assert matches[0]["metadata"]["path"] == "/b.txt"


def test_vector_store_statistics_and_inputs():
    store = VectorStoreServer(_docs_table(), embedder=fake_embedder)
    stats_q = table_from_rows(sch.schema_from_types(dummy=int), [(1,)])
    res = store.statistics_query(stats_q)
    rows = _result_rows(res)
    stats = rows[0]["result"].value
    assert stats["file_count"] == 3
    assert stats["last_modified"] == 300

    inputs_q = table_from_rows(
        sch.schema_from_types(metadata_filter=type(None),
                              filepath_globpattern=str),
        [(None, "/b*")])
    res2 = store.inputs_query(inputs_q)
    rows2 = _result_rows(res2)
    assert rows2[0]["result"].value == ["/b.txt"]


def test_vector_store_with_splitter():
    long_doc = ". ".join(f"sentence number {i} about topic{i % 3}"
                         for i in range(40)) + "."
    schema = sch.schema_from_types(data=str, _metadata=pw.Json)
    docs = table_from_rows(schema, [(long_doc, Json({"path": "/l.txt"}))])
    store = VectorStoreServer(
        docs, embedder=fake_embedder,
        splitter=splitters.TokenCountSplitter(min_tokens=10, max_tokens=40))
    chunks = store._graph["chunks"]
    df = table_to_pandas(chunks.select(text=pw.this.text))
    assert len(df) > 1  # split into multiple chunks
    for t in df["text"]:
        assert len(t.split()) <= 4 * 40


def test_token_count_splitter_bounds():
    sp = splitters.TokenCountSplitter(min_tokens=5, max_tokens=20)
    text = "word " * 200
    chunks = sp.chunk(text)
    assert all(5 <= len(c.split()) <= 20 for c, _ in chunks[:-1])
    assert sum(len(c.split()) for c, _ in chunks) == 200
    assert sp.chunk("") == []


def test_token_count_splitter_never_exceeds_max():
    """Regression: a short chunk must not absorb a long sentence past
    max_tokens (oversized chunks get truncated by the embedder)."""
    sp = splitters.TokenCountSplitter(min_tokens=50, max_tokens=100)
    text = " ".join(["a"] * 39) + ". " + " ".join(["b"] * 89) + "."
    chunks = sp.chunk(text)
    token_counts = [len(sp._tokenize(c)) for c, _ in chunks]
    assert all(n <= 100 for n in token_counts), token_counts
    assert sum(c.count("a") + c.count("b") for c, _ in chunks) == 128


def test_deck_retriever_builds():
    from pathway_tpu.xpacks.llm.question_answering import DeckRetriever

    store = VectorStoreServer(_docs_table(), embedder=fake_embedder)
    deck = DeckRetriever(FakeChat(), store)
    # the answer route takes retrieval-shaped queries
    queries = table_from_rows(
        deck.AnswerQuerySchema,
        [("systolic arrays", 1, None, None)])
    res = deck.answer_query(queries)
    rows = _result_rows(res)
    assert "systolic" in rows[0]["result"].value[0]["text"]


def test_default_cache_applies_to_unconfigured_udfs():
    from pathway_tpu.internals import udfs

    calls = []

    @pw.udf
    async def expensive(x: int) -> int:
        calls.append(x)
        return x * 2

    cache = udfs.InMemoryCache()
    udfs.set_default_cache(cache)
    try:
        fn = expensive.prepared_async()
        assert asyncio.run(fn(3)) == 6
        assert asyncio.run(fn(3)) == 6
        assert calls == [3]  # second call served from cache
    finally:
        udfs.set_default_cache(None)


def test_prepared_async_applies_retry():
    from pathway_tpu.internals import udfs

    attempts = []

    class FlakyChat(llms.BaseChat):
        def __init__(self):
            super().__init__(retry_strategy=udfs.FixedDelayRetryStrategy(
                max_retries=2, delay_ms=1))

        async def __wrapped__(self, messages, **kwargs):
            attempts.append(1)
            if len(attempts) < 2:
                raise RuntimeError("transient")
            return "ok"

    chat = FlakyChat()
    out = asyncio.run(chat.prepared_async()(
        [{"role": "user", "content": "hi"}]))
    assert out == "ok"
    assert len(attempts) == 2


def test_geometric_rag_strategy_escalates():
    chat = FakeChat(min_docs=4)
    answers = asyncio.run(answer_with_geometric_rag_strategy(
        ["q"], [[f"doc{i}" for i in range(8)]], chat,
        n_starting_documents=1, factor=2, max_iterations=5))
    assert answers[0] == "answer from 4 docs"
    assert chat.calls == [1, 2, 4]


def test_geometric_rag_strategy_gives_up():
    chat = FakeChat(min_docs=100)
    answers = asyncio.run(answer_with_geometric_rag_strategy(
        ["q"], [["doc"]], chat, n_starting_documents=1, factor=2,
        max_iterations=3))
    assert answers[0] == prompts.NO_INFO_ANSWER


def test_base_rag_answer_query():
    store = VectorStoreServer(_docs_table(), embedder=fake_embedder)
    rag = BaseRAGQuestionAnswerer(FakeChat(), store, search_topk=2)
    queries = table_from_rows(
        sch.schema_from_types(prompt=str, filters=type(None),
                              model=type(None), response_type=str),
        [("what do systolic arrays do", None, None, "long")])
    res = rag.answer_query(queries)
    rows = _result_rows(res)
    assert rows[0]["result"] == "answer from 2 docs"


def test_adaptive_rag_answer_query():
    store = VectorStoreServer(_docs_table(), embedder=fake_embedder)
    chat = FakeChat(min_docs=2)
    rag = AdaptiveRAGQuestionAnswerer(
        chat, store, n_starting_documents=1, factor=2, max_iterations=3)
    queries = table_from_rows(
        sch.schema_from_types(prompt=str, filters=type(None),
                              model=type(None), response_type=str),
        [("quick brown fox", None, None, "long")])
    res = rag.answer_query(queries)
    rows = _result_rows(res)
    assert rows[0]["result"] == "answer from 2 docs"
    assert chat.calls == [1, 2]


def test_rerank_topk_filter_and_encoder_reranker():
    docs = [f"d{i}" for i in range(5)]
    scores = [0.1, 0.9, 0.5, 0.7, 0.3]
    fn = rerankers.rerank_topk_filter.func
    kept, kept_scores = fn(docs, scores, 3)
    assert kept == ["d1", "d3", "d2"]
    assert kept_scores == [0.9, 0.7, 0.5]

    vocab = ["quick", "brown", "fox", "systolic", "arrays"]

    def vocab_embedder(text):
        words = str(text).lower().split()
        return np.array([float(w in words) for w in vocab])

    rr = rerankers.EncoderReranker(vocab_embedder)
    out = rr.func(["quick brown fox", "systolic arrays"],
                  ["brown fox", "brown fox"])
    assert out[0] > out[1]


def test_llm_reranker_with_fake_chat():
    class ScoreChat(llms.BaseChat):
        async def __wrapped__(self, messages, **kwargs):
            prompt = self._as_messages(messages)[-1]["content"]
            return "5" if "relevant-doc" in prompt else "1"

    rr = rerankers.LLMReranker(ScoreChat())
    score = asyncio.run(rr.func("relevant-doc text", "query"))
    assert score == 5.0
    score2 = asyncio.run(rr.func("other", "query"))
    assert score2 == 1.0


def test_jax_encoder_embedder():
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    emb = JaxEncoderEmbedder(config=EncoderConfig.tiny())
    assert emb.get_embedding_dimension() == 64
    out = emb.embed_batch(["hello world", "foo bar baz"])
    assert out.shape == (2, 64)
    # deterministic + distinct
    out2 = emb.embed_batch(["hello world", "foo bar baz"])
    np.testing.assert_array_equal(out, out2)
    assert not np.allclose(out[0], out[1])


def test_jax_embedder_in_pipeline():
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    emb = JaxEncoderEmbedder(config=EncoderConfig.tiny())
    store = VectorStoreServer(_docs_table(), embedder=emb)
    queries = table_from_rows(
        sch.schema_from_types(query=str, k=int, metadata_filter=type(None),
                              filepath_globpattern=type(None)),
        [("TPU systolic arrays multiply matrices fast", 1, None, None)])
    res = store.retrieve_query(queries)
    rows = _result_rows(res)
    matches = rows[0]["result"].value
    assert len(matches) == 1
    assert "systolic" in matches[0]["text"]


def test_geometric_rag_from_index_escalates():
    """The direct path retrieves max docs ONCE and escalates locally
    (reference question_answering.py:153): the fake chat needs 2 docs, so
    calls go 1 -> 2 with a single retrieval behind them."""
    from pathway_tpu.xpacks.llm.question_answering import (
        answer_with_geometric_rag_strategy_from_index)

    store = VectorStoreServer(_docs_table(), embedder=fake_embedder)
    chat = FakeChat(min_docs=2)
    queries = table_from_rows(
        sch.schema_from_types(prompt=str), [("quick brown fox",)])
    answer = answer_with_geometric_rag_strategy_from_index(
        queries.prompt, store.index, "text", chat,
        n_starting_documents=1, factor=2, max_iterations=3)
    rows = _result_rows(answer.table)
    assert rows[0]["answer"] == "answer from 2 docs"
    assert chat.calls == [1, 2]


def test_geometric_rag_from_index_returns_none_when_unanswerable():
    from pathway_tpu.xpacks.llm.question_answering import (
        answer_with_geometric_rag_strategy_from_index)

    store = VectorStoreServer(_docs_table(), embedder=fake_embedder)
    chat = FakeChat(min_docs=100)  # never satisfied
    queries = table_from_rows(
        sch.schema_from_types(prompt=str), [("quick brown fox",)])
    answer = answer_with_geometric_rag_strategy_from_index(
        queries.prompt, store.index, "text", chat,
        n_starting_documents=2, factor=2, max_iterations=2)
    rows = _result_rows(answer.table)
    assert rows[0]["answer"] is None
    # escalation 2 -> 4, capped by the 3 retrievable docs
    assert chat.calls == [2, 3]


def test_fused_device_embedding_index_path():
    """A device-capable embedder (encode_batch_device) makes the engine
    index take raw text: no UDF embedding column, embeddings born on
    device (ops/knn.py DeviceEmbeddingKnnIndex). Retrieval, metadata
    filters, and incremental updates must behave exactly like the
    UDF-embedded path."""
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.ops.knn import DeviceEmbeddingKnnIndex
    from pathway_tpu.stdlib.indexing import (
        default_brute_force_knn_document_index,
    )
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    emb = JaxEncoderEmbedder(config=EncoderConfig.tiny())
    docs = _docs_table()
    index = default_brute_force_knn_document_index(
        docs.data, docs, embedder=emb, dimensions=64,
        metadata_column=docs._metadata)
    assert index.inner_index.embeds_internally
    built = index.inner_index.factory().build()
    assert isinstance(built, DeviceEmbeddingKnnIndex)

    queries = table_from_rows(
        sch.schema_from_types(q=str), [("systolic arrays multiply",)])
    res = index.query_as_of_now(queries.q, number_of_matches=1,
                                collapse_rows=False)
    rows = _result_rows(res.select(data=res.data))
    assert len(rows) == 1 and "systolic" in rows[0]["data"]

    # same query against the classic UDF-embedded path must agree
    res2 = index.query_as_of_now(queries.q, number_of_matches=3,
                                 collapse_rows=False,
                                 metadata_filter="modified_at > `150`")
    rows2 = _result_rows(res2.select(data=res2.data))
    # the filter drops /a.txt (modified_at 100); both survivors return
    assert len(rows2) == 2
    assert not any("quick brown fox" in r["data"] for r in rows2)


def test_fused_index_handles_document_update_and_delete():
    """Retraction + re-add of a doc through the fused device-embedding
    index: a query after the update must see only the NEW text, and a
    deleted doc must stop matching."""
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.stdlib.indexing import (
        default_brute_force_knn_document_index,
    )
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    emb = JaxEncoderEmbedder(config=EncoderConfig.tiny())
    schema = sch.schema_from_types(doc_id=int, data=str)
    docs = table_from_rows(
        schema,
        [(1, "systolic arrays multiply matrices", 0, 1),
         (2, "ring attention rotates blocks", 0, 1),
         (1, "systolic arrays multiply matrices", 2, -1),  # doc replaced
         (1, "pallas kernels tile vmem", 2, 1)],
        is_stream=True)
    docs = docs.with_id_from(docs.doc_id)
    index = default_brute_force_knn_document_index(
        docs.data, docs, embedder=emb, dimensions=64)
    queries = table_from_rows(
        sch.schema_from_types(q=str), [("systolic arrays", 4, 1)],
        is_stream=True)
    res = index.query_as_of_now(queries.q, number_of_matches=2,
                                collapse_rows=False)
    rows = _result_rows(res.select(data=res.data))
    texts = {r["data"] for r in rows}
    assert "systolic arrays multiply matrices" not in texts
    assert texts <= {"ring attention rotates blocks",
                     "pallas kernels tile vmem"} and texts


# ---------------------------------------------------------------------------
# SlidesVectorStoreServer: per-slide indexing + metadata-rich /v1/inputs
# ---------------------------------------------------------------------------

def test_slides_vector_store_indexes_per_slide():
    from tests.test_doc_extract import make_pptx

    from pathway_tpu.xpacks.llm.vector_store import SlidesVectorStoreServer

    deck = make_pptx([["systolic arrays multiply matrices"],
                      ["ring attention rotates blocks"],
                      ["lazy dog jumps"]])
    schema = sch.schema_from_types(data=bytes, _metadata=pw.Json)
    docs = table_from_rows(
        schema, [(deck, Json({"path": "/deck.pptx", "b64_image": "xxxx"}))])
    store = SlidesVectorStoreServer(docs, embedder=fake_embedder)
    chunks = store._graph["chunks"]
    df = table_to_pandas(chunks.select(text=pw.this.text,
                                       metadata=pw.this.metadata))
    assert len(df) == 3                      # one chunk PER SLIDE
    metas = sorted((m.value for m in df["metadata"]),
                   key=lambda d: d["page"])
    assert [m["page"] for m in metas] == [1, 2, 3]
    assert all(m["total_pages"] == 3 for m in metas)
    assert all(m["path"] == "/deck.pptx" for m in metas)

    schema_q = sch.schema_from_types(query=str, k=int,
                                     metadata_filter=type(None),
                                     filepath_globpattern=type(None))
    queries = table_from_rows(
        schema_q, [("ring attention blocks", 1, None, None)])
    res = store.retrieve_query(queries)
    rows = _result_rows(res.select(result=pw.this.result))
    pw.run()
    (match,) = rows[0]["result"].value
    assert "ring attention" in match["text"]
    assert match["metadata"]["page"] == 2


def test_slides_vector_store_inputs_returns_metadata_dicts():
    from tests.test_doc_extract import make_pptx

    from pathway_tpu.xpacks.llm.vector_store import SlidesVectorStoreServer

    schema = sch.schema_from_types(data=bytes, _metadata=pw.Json)
    docs = table_from_rows(schema, [
        (make_pptx([["alpha"]]),
         Json({"path": "/a.pptx", "b64_image": "A" * 64, "owner": "ann"})),
        (make_pptx([["beta"]]),
         Json({"path": "/b.pptx", "image_base64": "B" * 64})),
    ])
    store = SlidesVectorStoreServer(docs, embedder=fake_embedder)
    inputs_q = table_from_rows(
        sch.schema_from_types(metadata_filter=type(None),
                              filepath_globpattern=type(None)),
        [(None, None)])
    rows = _result_rows(store.inputs_query(inputs_q))
    listing = sorted(rows[0]["result"].value, key=lambda d: d["path"])
    assert [d["path"] for d in listing] == ["/a.pptx", "/b.pptx"]
    assert listing[0]["owner"] == "ann"      # full metadata, not paths
    # bulky image payloads are stripped from the listing
    assert "b64_image" not in listing[0]
    assert "image_base64" not in listing[1]

    glob_q = table_from_rows(
        sch.schema_from_types(metadata_filter=type(None),
                              filepath_globpattern=str),
        [(None, "/b*")])
    rows2 = _result_rows(store.inputs_query(glob_q))
    assert [d["path"] for d in rows2[0]["result"].value] == ["/b.pptx"]


def test_parse_slides_non_deck_fallback():
    from pathway_tpu.xpacks.llm.vector_store import parse_slides

    out = parse_slides(b"plain notes, not a deck")
    assert len(out) == 1
    text, meta = out[0]
    assert "plain notes" in text
    assert meta["page"] == 1 and meta["total_pages"] == 1


# ---------------------------------------------------------------------------
# the index that embeds text itself hands a query's embedding from the
# encoder to the scan on the device (ops/knn.py DeviceEmbeddingKnnIndex)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ragged, uploads", [(True, 1), (False, 2)])
@pytest.mark.parametrize("metric", ["cos", "l2sq"])
def test_the_built_text_index_answers_as_its_inner_index_given_the_rows(
        metric, ragged, uploads):
    """The index the document-index factory builds for a device-capable
    embedder answers a text query with the keys and distances, to the
    last bit, that its inner index gives for the embedder's fetched rows:
    with the ragged packer (one buffer up) and the padded one (ids and
    lengths: two), and the transfers are counted on the search's span."""
    from pathway_tpu.engine.flight_recorder import FlightRecorder
    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.ops.knn import DeviceEmbeddingKnnIndex, KnnMetric
    from pathway_tpu.stdlib.indexing import (
        default_brute_force_knn_document_index,
    )
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    emb = JaxEncoderEmbedder(config=EncoderConfig.tiny(), ragged=ragged,
                             max_len=64)
    docs = _docs_table()
    index = default_brute_force_knn_document_index(
        docs.data, docs, embedder=emb, dimensions=64,
        metric=KnnMetric(metric), dtype="bfloat16")
    built = index.inner_index.factory().build()
    assert isinstance(built, DeviceEmbeddingKnnIndex)
    texts = ["the quick brown fox jumps over the lazy dog",
             "TPU systolic arrays multiply matrices fast",
             "ring attention rotates blocks around the interconnect"]
    built.add_batch([Pointer(i) for i in range(3)], texts)
    asked = ["systolic arrays multiply", "a lazy dog", texts[2]]
    rows = np.asarray(emb.encode_batch_device(asked), dtype=np.float32)
    rec = FlightRecorder.from_env(auto_on=True)
    try:
        by_text = built.search([(Pointer(100 + i), t, 2, None)
                                for i, t in enumerate(asked)])
    finally:
        rec.enabled = False
    by_rows = built.inner.search([(Pointer(100 + i), rows[i], 2, None)
                                  for i in range(3)])
    assert by_text == by_rows
    assert [r[0][0] for r in by_text] == [Pointer(1), Pointer(0),
                                          Pointer(2)]
    (search,) = [sp for sp in rec.spans() if sp[0] == "index.search"]
    # an L2 distance asks for the queries' vectors on the host: one
    # fetch more than the scan's
    assert search[5]["uploads"] == uploads
    assert search[5]["fetches"] == (1 if metric == "cos" else 2)
