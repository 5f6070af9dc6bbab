"""The system under test of the live-RAG cells, built through the entry
points a user calls and run on a thread of the benchmark's process:

    files -> pw.io.fs.read(format="plaintext_by_file", mode="streaming")
          -> VectorStoreServer(embedder=JaxEncoderEmbedder(...))
             with default_brute_force_knn_document_index
          -> run_server(threaded=True)  <-  HTTP on 127.0.0.1

From the program the benchmark takes the system, its counters and its
spans, and nothing else. Which embedder that is, what the reference is fed
and what a dispatch costs are the cell's architecture's
(``benchmark/models/<model>.py``); the harness relies on the embedder
protocol of :data:`EMBEDDER_PROTOCOL`, which is the program's own.
"""

from __future__ import annotations

import gc
import os
import socket
import time

import numpy as np

# keys of the benchmark's own, far from anything the engine derives: scratch
# query keys for direct searches and the filler rows of the fill
_SCRATCH_KEY = 1 << 62
_FILLER_KEY = 1 << 61
#: bytes of filler drawn and added a chunk: 524,288 rows of 384 bf16
_FILL_CHUNK_BYTES = (1 << 19) * 384 * 2

#: what the harness, the index that embeds text itself and ``pw.warmup``
#: call on an embedder (benchmark/README.md says what each is for)
EMBEDDER_PROTOCOL = ("params", "tokenizer", "ragged", "encode_batch_device",
                     "get_embedding_dimension")


def _require(obj, attrs, what: str) -> None:
    missing = [a for a in attrs if not hasattr(obj, a)]
    if missing:
        raise TypeError(f"{what} ({type(obj).__name__}) lacks "
                        f"{', '.join(missing)}: benchmark/README.md, "
                        f"\"The embedder protocol\"")


class System:
    def __init__(self, cell, seed: int, workdir: str, *,
                 flight_trace: str | None = None, log=print):
        self.config = cell.config
        self.model = cell.model          # benchmark/models/<model>.py
        self.reference = cell.reference  # benchmark/reference/<model>.py
        self.seed = seed
        self.log = log
        self.flight_trace = flight_trace
        self.watched = os.path.join(workdir, "watched")
        self.corpus_dir = os.path.join(self.watched, "corpus")
        self.live_dir = os.path.join(self.watched, "live")
        self.stage_dir = os.path.join(workdir, "stage")
        for d in (self.corpus_dir, self.live_dir, self.stage_dir):
            os.makedirs(d, exist_ok=True)
        self.embedder = None
        self.index = None
        self.filler_rows = 0
        self.runtime = None
        self.client = None
        self.base_url = None

    # -- build ---------------------------------------------------------------
    def make_embedder(self):
        """The architecture's embedder, holding the weights its reference
        makes from the seed."""
        emb = self.model.build(
            self.config, self.reference.weights(self.config, self.seed))
        _require(emb, EMBEDDER_PROTOCOL, "the embedder that "
                 f"{self.model.__name__}.build() returned")
        _require(emb.tokenizer, ("batch",), "its tokenizer")
        self.embedder = emb
        return emb

    def start(self) -> None:
        import pathway_tpu as pw
        from pathway_tpu.engine import streaming
        from pathway_tpu.ops.knn import KnnMetric
        from pathway_tpu.stdlib.indexing import (
            default_brute_force_knn_document_index)
        from pathway_tpu.xpacks.llm.vector_store import (VectorStoreClient,
                                                         VectorStoreServer)

        emb = self.embedder or self.make_embedder()
        ix = self.config["index"]
        source = pw.io.fs.read(self.watched, format="plaintext_by_file",
                               mode="streaming", with_metadata=True)

        def build_index(chunks):
            return default_brute_force_knn_document_index(
                chunks.text, chunks, embedder=emb,
                dimensions=emb.get_embedding_dimension(),
                metadata_column=chunks.metadata,
                metric=KnnMetric(ix["metric"]), dtype=ix["dtype"],
                reserved_space=ix["reserved_rows"])

        server = VectorStoreServer(source, embedder=emb,
                                   index_builder=build_index)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        run_kwargs = {}
        if self.flight_trace is not None:
            # the request tracker rides the flight recorder, which a trace
            # path turns on: the program's own switch, in traced runs only
            run_kwargs["trace_path"] = self.flight_trace
        # with_cache=False: the default DiskCache writes ./Cache under the
        # working directory, and a run leaves nothing in the checkout
        server.run_server(host="127.0.0.1", port=port, threaded=True,
                          with_cache=False, **run_kwargs)
        self.base_url = f"http://127.0.0.1:{port}"
        self.client = VectorStoreClient("127.0.0.1", port, timeout=120)
        deadline = time.monotonic() + 120
        while True:
            try:
                self.client.get_vectorstore_statistics()
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise TimeoutError("the server did not start listening")
                time.sleep(0.05)
        (self.runtime,) = streaming.live_runtimes()
        (self.index,) = [node.op.index for node in
                         self.runtime.runner.graph.nodes
                         if hasattr(node.op, "index")]
        if not hasattr(self.index, "embedder"):
            raise RuntimeError(f"the program chose {type(self.index).__name__}"
                               f", not the index that embeds text itself")
        self.log(f"system: {type(self.index).__name__}"
                 f"({type(self.index.inner).__name__}) "
                 f"capacity_rows={self._pages()['capacity_rows']} "
                 f"ragged={emb.ragged}")

    def stop(self) -> None:
        from pathway_tpu.engine import streaming

        streaming.stop_all()

    # -- what the program says about itself ------------------------------------
    def _store(self):
        return self.index.inner

    def _pages(self) -> dict:
        return self._store().page_stats()

    def file_count(self) -> int:
        """``/v1/statistics`` ``file_count`` over HTTP (0 before the first
        document)."""
        return int(self.client.get_vectorstore_statistics()["file_count"]
                   or 0)

    def rows(self) -> int:
        """Documents in the index: its rows less the filler's."""
        return len(self.index) - self.filler_rows

    def counters(self) -> dict:
        """Counts read in-process, for deltas over the window."""
        pages = self._pages()
        return {
            "rows": self.rows(),
            "fused_batches": self.index.fused_batches,
            "fused_fallbacks": self.index.fused_fallbacks,
            "upload_rows_total": self._store().upload_rows_total,
            "extents": pages["extents"],
            "grow_events": pages["grow_events"],
            "capacity_rows": pages["capacity_rows"],
            "bridge": self.runtime.scheduler.bridge_stats(),
        }

    def tracker(self):
        """The run's request tracker (None unless the flight recorder is
        on)."""
        rec = self.runtime.recorder
        return rec.requests if rec is not None else None

    # -- set-up -----------------------------------------------------------------
    def fill(self) -> None:
        """Bring the resident index to ``index.rows`` live rows: filler
        vectors made on the device from the seed and added to the served
        index under keys of the benchmark's own, which no answer may name.
        The program has no bulk preload, so each row pays the host's
        bookkeeping (a ``Pointer``, two dict entries, a set entry). The
        interpreter's collector is off meanwhile (ten million new tracked
        objects set off seventeen full collections, each walking all there
        are so far) and runs once at the end: the next full collection is
        then a quarter of the heap away whatever the seed, as it nearly
        always is in a deployment that holds this many documents, and
        cannot fall into one run's window and not the next's."""
        import jax
        import jax.numpy as jnp

        from pathway_tpu.internals.keys import Pointer

        store, dim = self._store(), self.embedder.get_embedding_dimension()
        rows = self.config["index"]["rows"]
        chunk = min(rows, _FILL_CHUNK_BYTES // (2 * dim))   # bf16 rows
        gen = jax.jit(lambda key: jax.random.uniform(
            key, (chunk, dim), jnp.bfloat16, -1.0, 1.0))
        key = jax.random.PRNGKey(self.seed + 1)
        gc.disable()
        try:
            for ci, base in enumerate(range(0, rows, chunk)):
                m = min(chunk, rows - base)
                vecs = gen(jax.random.fold_in(key, ci))
                store.add_batch_device(
                    list(map(Pointer, range(_FILLER_KEY + base,
                                            _FILLER_KEY + base + m))),
                    vecs if m == chunk else vecs[:m])
                self.filler_rows += m
            store.drain()
        finally:
            gc.enable()
        gc.collect()

    def warm(self, *, k: int | None, query_batch_max: int,
             query_texts: list[str]) -> None:
        """Every shape the mix will meet, compiled or loaded before the
        window: ``pw.warmup`` over the embedder's shapes (the ingest
        dispatch, and with ``k`` the plain encoder of the query path), then
        what ``pw.warmup`` does not know of — the scan at each batch size a
        tick can hold."""
        import pathway_tpu as pw

        pw.warmup(self.embedder, index=self.index, ks=(k,) if k else ())
        if k and query_batch_max:
            self.warm_queries(query_texts, k, query_batch_max)

    def warm_queries(self, texts: list[str], k: int, max_batch: int) -> None:
        """Searches of 1..``max_batch`` queries on the served index object,
        as a tick holding that many makes them: the scan compiles per batch
        size, and nothing buckets it. The ragged encoder's output is sliced
        to the number of queries, one small program per (sequence bucket,
        queries): short queries share one packed sequence, a read-your-write
        query (a whole document) can fill one alone, so each batch is also
        walked with one and with two long texts in front."""
        from pathway_tpu.internals.keys import Pointer

        width = self.config["serving"]["max_len"]
        long = " ".join(["word0"] * (width - 2))
        for b in range(1, min(max_batch, len(texts)) + 1):
            for n_long in range(0, min(b, 3)):
                batch = [long] * n_long + texts[:b - n_long]
                self.index.search([(Pointer(_SCRATCH_KEY + i), q, k, None)
                                   for i, q in enumerate(batch)])

    # -- traced runs --------------------------------------------------------------
    def instrument(self, spans: dict) -> None:
        """Spans of the benchmark's own around the calls into each layer, on
        these instances only: ``spans[name]`` gets (t0, t1, meta) per call,
        and the profiler's trace a ``bench.<name>`` span."""
        import jax

        def wrap(obj, attr: str, name: str, meta) -> None:
            fn = getattr(obj, attr)

            def wrapper(*args, **kwargs):
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench." + name):
                    out = fn(*args, **kwargs)
                spans.setdefault(name, []).append(
                    (t0, time.perf_counter(), meta(args, out)))
                return out

            setattr(obj, attr, wrapper)

        emb = self.embedder
        _require(emb, ("pack_ragged" if emb.ragged else "pack_tokens",),
                 "the embedder, whose packer a traced run times,")
        wrap(emb.tokenizer, "batch", "tokenizer.batch",
             lambda a, out: {"texts": len(a[0])})
        if emb.ragged:
            wrap(emb, "pack_ragged", "pack",
                 lambda a, out: {"texts": len(a[0]), "ragged": True,
                                 "shapes": [c[0][0].shape for c in out]})
        else:
            wrap(emb, "pack_tokens", "pack",
                 lambda a, out: {"texts": len(a[0]), "ragged": False,
                                 "shapes": [out[0].shape]})
        wrap(self.index, "add_batch", "index.add_batch",
             lambda a, out: {"rows": len(a[0])})
        wrap(self.index, "search", "index.search",
             lambda a, out: {"queries": len(a[0])})

    def encoder_cost(self, shape: tuple, ragged: bool) -> tuple[float, float]:
        """(flops, bytes) of one encoder dispatch of packed ``shape``."""
        return self.model.dispatch_cost(self.config, shape, ragged)

    def scan_cost(self, queries: int) -> tuple[float, float]:
        """(flops, bytes) of one scan: every established row (every extent
        is established once written)."""
        from benchmark.lib import costs

        rows = self._pages()["capacity_rows"]
        itemsize = {"float32": 4, "bfloat16": 2,
                    "int8": 1}[self.config["index"]["dtype"]]
        return costs.knn_search_cost(
            queries, rows, self.embedder.get_embedding_dimension(), itemsize)

    # -- the reference's inputs ---------------------------------------------------
    def tokens(self, texts: list[str]) -> tuple[np.ndarray, np.ndarray]:
        """(ids, lengths) of ``texts``, as the reference takes them."""
        return self.model.tokens(self.embedder, self.config, texts)

    def served_embeddings(self, texts: list[str]) -> np.ndarray:
        """What the program's encoder path makes of ``texts`` (the packer
        and precision the configuration serves with)."""
        return np.asarray(self.embedder.encode_batch_device(texts),
                          dtype=np.float32)
