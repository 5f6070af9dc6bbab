"""The system under test of the live-RAG cells, built through the entry
points a user calls and run on a thread of the benchmark's process:

    files -> pw.io.fs.read(format="plaintext_by_file", mode="streaming")
          -> VectorStoreServer(embedder=JaxEncoderEmbedder(...))
             with default_brute_force_knn_document_index
          -> run_server(threaded=True)  <-  HTTP on 127.0.0.1

From the program the benchmark takes the system, its counters and its
spans, and nothing else.
"""

from __future__ import annotations

import gc
import inspect
import os
import socket
import time

import numpy as np

# keys of the benchmark's own, far from anything the engine derives: scratch
# query keys for direct searches and the filler rows of the fill
_SCRATCH_KEY = 1 << 62
_FILLER_KEY = 1 << 61


class System:
    def __init__(self, config: dict, seed: int, workdir: str, *,
                 flight_trace: str | None = None, log=print):
        self.config = config
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
        self.encoder_config = None
        self.index = None
        self.filler_rows = 0
        self.runtime = None
        self.client = None
        self.base_url = None

    # -- build ---------------------------------------------------------------
    def make_embedder(self):
        """Seeded weights made on the device in one jitted call, in the type
        the program serves them in; the synthetic WordPiece vocab."""
        import jax
        import jax.numpy as jnp

        from pathway_tpu.models.encoder import EncoderConfig, init_params
        from pathway_tpu.models.tokenizer import (WordPieceTokenizer,
                                                  make_synthetic_vocab)
        from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

        c, serving = self.config, self.config["serving"]
        cfg = EncoderConfig(
            vocab_size=c["vocab_size"], hidden=c["hidden_size"],
            layers=c["num_hidden_layers"], heads=c["num_attention_heads"],
            intermediate=c["intermediate_size"],
            max_len=c["max_position_embeddings"],
            type_vocab_size=c["type_vocab_size"],
            layer_norm_eps=c["layer_norm_eps"], pooling=c["pooling"],
            normalize=c["normalize"],
            compute_dtype=getattr(jnp, serving["compute_dtype"]))
        params = jax.jit(lambda key: init_params(key, cfg))(
            jax.random.PRNGKey(self.seed))
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
        self.encoder_config = cfg
        self.embedder = JaxEncoderEmbedder(
            config=cfg, params=params, tokenizer=tokenizer,
            max_len=serving["max_len"], **kwargs)
        return self.embedder

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
                 f"ragged={getattr(emb, 'ragged', None)}")

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

        store, dim = self._store(), self.encoder_config.hidden
        rows, chunk = self.config["index"]["rows"], 1 << 19
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
        wrap(emb.tokenizer, "batch", "tokenizer.batch",
             lambda a, out: {"texts": len(a[0])})
        if getattr(emb, "ragged", False):
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
        from benchmark.lib import costs

        cfg = self.encoder_config
        kw = dict(hidden=cfg.hidden, intermediate=cfg.intermediate,
                  layers=cfg.layers)
        if ragged:
            return costs.segment_attention_cost(*shape, heads=cfg.heads, **kw)
        return costs.encoder_cost(*shape, **kw)

    def scan_cost(self, queries: int) -> tuple[float, float]:
        """(flops, bytes) of one scan: every established row (every extent
        is established once written)."""
        from benchmark.lib import costs

        rows = self._pages()["capacity_rows"]
        itemsize = {"float32": 4, "bfloat16": 2,
                    "int8": 1}[self.config["index"]["dtype"]]
        return costs.knn_search_cost(queries, rows,
                                     self.encoder_config.hidden, itemsize)

    # -- the reference's inputs ---------------------------------------------------
    def tokens(self, texts: list[str]) -> tuple[np.ndarray, np.ndarray]:
        """(ids, lengths) of ``texts`` from the program's tokenizer, padded
        to the serving width."""
        width = self.config["serving"]["max_len"]
        ids, mask = self.embedder.tokenizer.batch(
            [t or "." for t in texts], max_len=width)
        ids = np.pad(ids, ((0, 0), (0, width - ids.shape[1])))
        return ids.astype(np.int32), mask.sum(axis=1).astype(np.int32)

    def served_embeddings(self, texts: list[str]) -> np.ndarray:
        """What the program's encoder path makes of ``texts`` (the packer
        and precision the configuration serves with)."""
        return np.asarray(self.embedder.encode_batch_device(texts),
                          dtype=np.float32)
