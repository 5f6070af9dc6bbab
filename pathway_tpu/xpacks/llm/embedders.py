"""Embedders — UDFs mapping text columns to embedding vectors.

Same API family as the reference (xpacks/llm/embedders.py: OpenAIEmbedder:83,
LiteLLMEmbedder:178, SentenceTransformerEmbedder:268, GeminiEmbedder:328;
dimension probing via one call :63), plus the TPU-native flagship:
``JaxEncoderEmbedder`` runs pathway_tpu/models/encoder.py under jit with
**columnar batch dispatch** (UDF batch=True) — whole engine batches are
tokenized and encoded in one device call, never per row.
"""

from __future__ import annotations

import asyncio
import collections
import functools
import operator
import threading
import weakref
from time import perf_counter as _perf_counter
from typing import Any

import numpy as np

from pathway_tpu.engine import flight_recorder as _fr
from pathway_tpu.internals import udfs
from pathway_tpu.xpacks.llm._utils import _import_or_raise


class BaseEmbedder(udfs.UDF):
    """Embedder base: callable on a column; knows its output dimension."""

    def get_embedding_dimension(self, **kwargs) -> int:
        """Probe the dimension with one call (reference embedders.py:63)."""
        result = self.func(".", **kwargs)
        if asyncio.iscoroutine(result):
            result = asyncio.run(result)
        arr = np.asarray(result)
        if arr.ndim == 2:  # batch embedder probed with a single item
            arr = arr[0]
        return int(arr.shape[0])


#: forwards of a model that returns ``aux`` which the device may hold
#: beside the one just dispatched before the host waits: one runs, one is
#: queued, and the host packs the next. The runtime queues dozens without
#: a wait, so the first legs behind a slow model retired in a tenth of
#: their device time, ``DeviceBackpressure`` read 5 ms a document for 50,
#: and the ticks after a release held 8, 8, 8, 16, 24, 36, 36 documents:
#: seven seconds of work for a budget of 0.4 s a leg, then legs of two
#: documents for ten seconds (my chip runs, PR 33). Waiting here makes a
#: leg's host time its device time to within these dispatches
DISPATCHES_AHEAD = 2

# live embedders that hold an expert-load sum (each joins at its first
# ``note_producer_aux``): /metrics reads them (engine/http_server.py)
# without a reference plumbed through the graph
_AUX_EMBEDDERS: "weakref.WeakSet" = weakref.WeakSet()


def expert_load_stats() -> dict | None:
    """Expert load of the live embedders whose model routes tokens to
    experts: {"max", "mean" tokens an expert, "dispatches",
    "full_buffer_layers": expert-layer executions whose pair buffer took
    its full length, "buffer_rows_mean": the buffer's rows an execution
    (ops/moe.py), where a router has identity experts "zero_pairs",
    "pairs": the chosen pairs of real tokens that took one, and all of
    them, and where a model chooses the keys a query attends over
    "selected_pairs", "visible_pairs": the (query, key) pairs its attention
    layers attended over, of those they could see} over all of them; None
    where there is none. Fetches from the device."""
    loads = [ld for e in list(_AUX_EMBEDDERS)
             if (ld := e.expert_load()) is not None]
    if not loads:
        return None
    tokens = np.concatenate([ld["tokens_per_expert"] for ld in loads])
    layers = sum(ld["expert_layers"] for ld in loads)
    stats = {"max": float(tokens.max()), "mean": float(tokens.mean()),
             "dispatches": sum(ld["dispatches"] for ld in loads),
             "full_buffer_layers": sum(ld["full_buffer_layers"]
                                       for ld in loads),
             "buffer_rows_mean": sum(ld["buffer_rows"] for ld in loads)
             / layers if layers else 0.0}
    for names in (("zero_pairs", "pairs"),
                  ("selected_pairs", "visible_pairs")):
        if any(names[0] in ld for ld in loads):
            stats.update({name: sum(ld.get(name, 0.0) for ld in loads)
                          for name in names})
    return stats


# live embedders whose model has attention layers the packer counts the
# work of (each joins at its first ``dispatch_work``)
_ATTENTION_EMBEDDERS: "weakref.WeakSet" = weakref.WeakSet()


def attention_tile_stats() -> dict | None:
    """Key blocks the blocked attention of the live embedders ran
    (``tiles_run``) of all up to the diagonal (``tiles_all``), summed over
    every fused dispatch so far (ops/attention.py ``attention_work``); None
    where no embedder counts them."""
    totals = [e.attention_tiles() for e in list(_ATTENTION_EMBEDDERS)]
    if not totals:
        return None
    return {"tiles_run": sum(t[0] for t in totals),
            "tiles_all": sum(t[1] for t in totals)}


@functools.lru_cache(maxsize=None)
def _first_rows_fn():
    """Jitted ``first_rows(rows, n) -> rows[:n]``, ``n`` static: the
    real documents of a ragged dispatch's padded rows. One program a
    (padded rows, n), as the eager slice it replaces, which also uploaded
    a start index for each dimension at every call."""
    import jax

    def first_rows(rows, n):
        return rows[:n]

    return jax.jit(first_rows, static_argnums=1)


class JaxEncoderEmbedder(BaseEmbedder):
    """TPU-native embedder over an in-repo JAX model.

    Tokenizes with models.tokenizer (HashTokenizer by default, or a local HF
    tokenizer), bf16 forward under jit, sequence-length bucketing to bound
    recompilation. This replaces the reference's torch
    SentenceTransformerEmbedder as the local-model path.

    The architecture is the ``config`` object's: it supplies ``encode``
    (padded batch), ``encode_ragged`` (ragged-packed rows), ``init_params``,
    ``cost`` (a forward's operations and bytes for the engine's profiler,
    or None), ``hidden``, ``vocab_size``, ``max_len`` and ``pooling``
    (models/encoder.py ``EncoderConfig``, the default;
    models/decoder.py ``DecoderConfig``). A forward may return ``(embeddings,
    aux)``: ``aux`` is a decoder's expert-layer counters, a dict of small
    device arrays (``tokens_per_expert``; ``buffer``: executions, those at
    the full pair-buffer length, buffer rows; with identity experts also
    ``zero_pairs``, ``pairs``), summed on the device and fetched only by
    :meth:`expert_load`.

    ``ragged_max_seqs``: packed rows a ragged dispatch holds at most
    (default ``PATHWAY_RAGGED_MAX_SEQS``, else 8).
    """

    _BUCKETS = (32, 64, 128, 256, 512)

    def __init__(self, *, model: str | None = None, config=None,
                 params=None, tokenizer=None,
                 seed: int = 0, max_len: int = 512,
                 ragged: bool | None = None,
                 ragged_max_seqs: int | None = None,
                 call_kwargs: dict = {}, **kwargs):
        kwargs.setdefault("batch", True)
        kwargs.setdefault("deterministic", True)
        kwargs.setdefault("device", True)  # pipeline via the device bridge
        super().__init__(**kwargs)
        import os

        import jax

        from pathway_tpu.warmup import enable_compilation_cache

        # persistent XLA cache: the ~18 bucket shapes compile once per
        # machine, not once per process
        enable_compilation_cache()

        from pathway_tpu.models.encoder import EncoderConfig
        from pathway_tpu.models.tokenizer import HashTokenizer

        if model is not None:
            # name-based convenience, like the reference's
            # SentenceTransformerEmbedder(model=...): loads the checkpoint
            # (weights + config + WordPiece vocab) from the local HF cache
            from pathway_tpu.models.hf_loader import load_model

            params, config, tokenizer = load_model(model)
        self.config = config or EncoderConfig.bge_small()
        self.params = params if params is not None else \
            self.config.init_params(jax.random.PRNGKey(seed))
        self.tokenizer = tokenizer or HashTokenizer(
            vocab_size=self.config.vocab_size, max_len=max_len)
        self.max_len = min(max_len, self.config.max_len)
        # the expert layers' counters, summed over every dispatch of a
        # model with routed experts: device arrays, never fetched in a tick
        self._aux_lock = threading.Lock()
        self._aux_sum = None
        self._aux_dispatches = 0
        # what the last ``DISPATCHES_AHEAD`` forwards returned
        self._aux_ahead: collections.deque = collections.deque()
        # key blocks the attention layers ran, of all up to the diagonal
        self._attention_tiles = [0, 0]

        def add_aux(total, aux):
            return jax.tree.map(operator.add, total, aux)

        # one program and one dispatch however many arrays ``aux`` holds
        self._add_aux = jax.jit(add_aux)
        # packed hot path: int16 ids + per-row lengths instead of int32
        # ids + a (B, S) bool mask — a quarter of the host→device bytes;
        # the mask is rebuilt on device (iota < len). Usable whenever the
        # vocab fits int16 (BGE's 30522 does). One implementation
        # (device_producer) serves both this jit and the fused ingest.
        self._encode_packed = jax.jit(self.device_producer)
        self._pack_ids = self.config.vocab_size <= 32767
        # ragged batching (PATHWAY_RAGGED_ENCODER=1 or ragged=True):
        # variable-length docs pack back-to-back into fixed-width
        # sequences with a doc-map vector instead of per-width padding —
        # the ~18 width-bucket compiles collapse to the handful of
        # sequence-count buckets in ragged_buckets()
        if ragged is None:
            ragged = os.environ.get(
                "PATHWAY_RAGGED_ENCODER", "0").lower() in (
                "1", "true", "on", "yes")
        self.ragged = bool(ragged)
        from pathway_tpu.internals.config import _env_int

        if ragged_max_seqs is None:
            ragged_max_seqs = _env_int("PATHWAY_RAGGED_MAX_SEQS", 8)
        self._ragged_max_seqs = max(1, int(ragged_max_seqs))
        # docs-per-sequence cap bounds the padded doc dimension of a chunk
        # (W//16: a doc is never shorter than CLS+token+SEP anyway)
        self._ragged_doc_cap = max(1, self.max_len // 16)

        # the plain ragged encoder takes a chunk as ONE int32 buffer (one
        # upload, :meth:`encode_ragged_chunk`) and splits it at static
        # offsets; named as the method it calls, since the name is the
        # XLA module's (``jit_ragged_device_producer``)
        def ragged_device_producer(params, flat):
            return self.ragged_device_producer(
                params, *self._split_ragged(flat))

        self._encode_ragged = jax.jit(ragged_device_producer)

    def _bucket(self, n: int) -> int:
        """Pad target for a batch whose longest row has ``n`` tokens.
        MXU time scales with padded tokens, so buckets are multiples of
        16 up to 64 then multiples of 32 — tight enough to not waste
        ~30% of the forward on padding (pow-2 buckets would), coarse
        enough to bound recompilation at ~18 shapes."""
        if n <= 64:
            b = max(16, -(-n // 16) * 16)
        else:
            b = -(-n // 32) * 32
        return min(b, self.max_len)

    def bucket_widths(self) -> list[int]:
        """Every padded width ``_bucket`` can produce for this ``max_len``
        (~18 shapes at 512) — the exact compile set ``pw.warmup`` walks so
        a warmed process (or a persistent-cache hit) never compiles the
        encoder inside a serving tick."""
        widths: list[int] = []
        w = 16
        while w <= min(64, self.max_len):
            widths.append(w)
            w += 16
        w = 96
        while w < self.max_len:
            widths.append(w)
            w += 32
        if self.max_len not in widths:
            widths.append(self.max_len)
        return widths

    def pack_tokens(self, texts: list[str]) -> tuple[np.ndarray, np.ndarray]:
        """Tokenize + bucket-pad, returning ``(ids, lens)`` ready for the
        packed device producer — int16 ids when the vocab fits. While a
        flight recorder is on, the call is the span ``embedder.pack`` and
        the tokenizer's part of it ``embedder.tokenize``."""
        spans = _fr.recording()
        if spans:
            t0 = _perf_counter()
        ids, mask = self.tokenizer.batch(
            [t or "." for t in texts], max_len=self.max_len)
        if spans:
            t_tokens = _perf_counter()
        pad_to = self._bucket(ids.shape[1])
        if ids.shape[1] < pad_to:
            ids = np.pad(ids, ((0, 0), (0, pad_to - ids.shape[1])))
        else:
            ids, mask = ids[:, :pad_to], mask[:, :pad_to]
        lens = mask.sum(axis=1).astype(np.int32)
        ids = ids.astype(np.int16 if self._pack_ids else np.int32)
        if spans:
            self._pack_spans(t0, t_tokens, len(texts), int(lens.sum()),
                             [ids.shape])
        return ids, lens

    @staticmethod
    def _pack_spans(t0: float, t_tokens: float, texts: int, tokens: int,
                    shapes: list[tuple]) -> None:
        """The packer's two spans: ``embedder.tokenize`` ``[t0, t_tokens]``
        inside ``embedder.pack`` ``[t0, now]``, which made dispatches of
        ``shapes`` (rows, width) holding ``tokens`` real tokens."""
        _fr.live_span("embedder.tokenize", t0, t_tokens, texts=texts,
                      tokens=tokens)
        _fr.live_span("embedder.pack", t0, _perf_counter(), texts=texts,
                      rows=sum(r for r, _w in shapes),
                      slots=sum(r * w for r, w in shapes), tokens=tokens)

    def device_producer(self, params, ids, lens):
        """Pure (traceable) forward over packed tokens: mask rebuilt on
        device. ops/knn.py's fused ingest composes this with the slab
        scatter into ONE donated dispatch."""
        import jax.numpy as jnp

        ids32 = ids.astype(jnp.int32)
        mask = jnp.arange(ids32.shape[1])[None, :] < lens[:, None]
        return self.config.encode(params, ids32, mask)

    def ragged_device_producer(self, params, ids, doc_map, pos_ids,
                               doc_seq, doc_off):
        """Pure (traceable) forward over a ragged-packed chunk (the
        config's ``encode_ragged``) — the fused-ingest producer of the
        ragged path, returning (n_docs_padded, hidden)."""
        return self.config.encode_ragged(params, ids, doc_map, pos_ids,
                                         doc_seq, doc_off)

    def note_producer_aux(self, aux) -> None:
        """Sum what a forward returned beside its embeddings into the
        device arrays this embedder keeps (one small asynchronous
        dispatch, no transfer), and wait for the forward
        ``DISPATCHES_AHEAD`` before this one: ``aux`` is ready when its
        forward is done."""
        import jax

        with self._aux_lock:
            if self._aux_sum is None:
                _AUX_EMBEDDERS.add(self)
                self._aux_sum = aux
            else:
                self._aux_sum = self._add_aux(self._aux_sum, aux)
            self._aux_dispatches += 1
            self._aux_ahead.append(aux)
            done = self._aux_ahead.popleft() \
                if len(self._aux_ahead) > DISPATCHES_AHEAD else None
        if done is not None:
            jax.block_until_ready(done)

    def _embeddings(self, out):
        """A forward's embeddings, its ``aux`` (if any) noted."""
        if isinstance(out, tuple):
            out, aux = out
            self.note_producer_aux(aux)
        return out

    def expert_load(self) -> dict | None:
        """Tokens each held expert took, summed over every dispatch so
        far and every layer, and the pair buffer's counters (ops/moe.py:
        expert-layer executions, those that took the full length, the
        buffer's rows summed over them) and, where the router has identity
        experts, ``zero_pairs`` and ``pairs`` (the chosen pairs of real
        tokens that took one, and all of them), where the model chooses its
        keys ``selected_pairs`` and ``visible_pairs`` (the device's own
        count of the pairs attended over, of those visible, all attention
        layers), fetched from the device
        now (the one transfer: call it from a metrics request, not from a
        tick). None where the model routes nothing."""
        with self._aux_lock:
            total, dispatches = self._aux_sum, self._aux_dispatches
        if total is None:
            return None
        layers, full, rows = np.asarray(total["buffer"]).tolist()
        load = {"tokens_per_expert": np.asarray(total["tokens_per_expert"]),
                "dispatches": dispatches, "expert_layers": int(layers),
                "full_buffer_layers": int(full), "buffer_rows": rows}
        load.update({name: float(total[name])
                     for name in ("zero_pairs", "pairs", "selected_pairs",
                                  "visible_pairs") if name in total})
        return load

    def dispatch_work(self, args: tuple) -> dict:
        """What the attention layers have to do in the ragged dispatch of
        ``args`` (a chunk of :meth:`pack_ragged`), counted on the host from
        its documents' places: ``attn_pairs_full``, ``attn_pairs_window``,
        ``attn_tiles_run``, ``attn_tiles_all``, ``attn_query_block`` (the
        cores' own, from the configuration's head counts) and, where the
        model chooses its keys, ``attn_pairs_indexed`` and
        ``attn_pairs_selected`` (ops/attention.py ``attention_work``),
        which the ``embedder.dispatch`` span carries
        and :meth:`attention_tiles` sums. Empty where the model's config
        names no attention layers."""
        windows = getattr(self.config, "attention_windows", None)
        if not windows:
            return {}
        from pathway_tpu.ops.attention import attention_work

        work = attention_work(
            args[1], args[2], windows,
            *(getattr(self.config, "attention_index", None) or ()),
            rep=self.config.attention_rep)
        with self._aux_lock:
            _ATTENTION_EMBEDDERS.add(self)
            self._attention_tiles[0] += work["attn_tiles_run"]
            self._attention_tiles[1] += work["attn_tiles_all"]
        return work

    def attention_tiles(self) -> tuple[int, int]:
        """(key blocks run, all up to the diagonal) over every fused
        dispatch so far."""
        with self._aux_lock:
            return tuple(self._attention_tiles)

    def ragged_buckets(self) -> list[int]:
        """Sequence-count buckets the ragged path can dispatch: powers of
        two up to the per-chunk cap (full chunks all share ONE shape).
        This is the ENTIRE ragged compile set — len ≤ 6 vs ~18 width
        buckets — and the set ``pw.warmup`` walks when ragged is on."""
        out, b = [], 1
        while b < self._ragged_max_seqs:
            out.append(b)
            b *= 2
        out.append(self._ragged_max_seqs)
        return out

    def pack_ragged(self, texts: list[str]) -> list[tuple]:
        """Greedy first-fit packing of tokenized docs into fixed-width
        sequences, chunked at ``_ragged_max_seqs`` sequences per dispatch.

        Returns ``[(args, n_docs, n_docs_padded), ...]`` per chunk, docs
        in input order, where ``args = (ids, doc_map, pos_ids, doc_seq,
        doc_off)`` feed ragged_device_producer and ``n_docs_padded`` is
        its static output row count (pad rows carry doc_map -1 and are
        dropped by the caller / the fused scatter). ``doc_off`` is the
        offset of the token the model pools: a document's first, or under
        ``pooling: "last"`` its last. The spans are ``pack_tokens``'s."""
        spans = _fr.recording()
        if spans:
            t0 = _perf_counter()
        ids, mask = self.tokenizer.batch(
            [t or "." for t in texts], max_len=self.max_len)
        if spans:
            t_tokens = _perf_counter()
        lens = mask.sum(axis=1).astype(np.int64)
        W, cap = self.max_len, self._ragged_doc_cap
        pool_last = self.config.pooling == "last"
        # assign each doc a (sequence, offset) first-fit in order
        seq_of = np.empty(len(texts), np.int64)
        off_of = np.empty(len(texts), np.int64)
        seq, fill, docs_in_seq = 0, 0, 0
        for d, n in enumerate(lens):
            n = int(n)
            if fill + n > W or docs_in_seq >= cap:
                seq, fill, docs_in_seq = seq + 1, 0, 0
            seq_of[d], off_of[d] = seq, fill
            fill += n
            docs_in_seq += 1
        n_seqs_total = seq + 1
        chunks: list[tuple] = []
        max_seqs = self._ragged_max_seqs
        buckets = self.ragged_buckets()
        d0 = 0
        for s0 in range(0, n_seqs_total, max_seqs):
            s1 = min(s0 + max_seqs, n_seqs_total)
            n_seqs = next(b for b in buckets if b >= s1 - s0)
            d1 = d0
            while d1 < len(texts) and seq_of[d1] < s1:
                d1 += 1
            n_docs = d1 - d0
            n_pad = n_seqs * cap
            c_ids = np.zeros((n_seqs, W), np.int32)
            c_map = np.full((n_seqs, W), -1, np.int32)
            c_pos = np.zeros((n_seqs, W), np.int32)
            c_dseq = np.zeros((n_pad,), np.int32)
            c_doff = np.zeros((n_pad,), np.int32)
            for j, d in enumerate(range(d0, d1)):
                n = int(lens[d])
                s, o = int(seq_of[d]) - s0, int(off_of[d])
                c_ids[s, o:o + n] = ids[d, :n]
                c_map[s, o:o + n] = j
                c_pos[s, o:o + n] = np.arange(n)
                c_dseq[j], c_doff[j] = s, o + n - 1 if pool_last else o
            chunks.append(((c_ids, c_map, c_pos, c_dseq, c_doff),
                           n_docs, n_pad))
            d0 = d1
        if spans:
            self._pack_spans(t0, t_tokens, len(texts), int(lens.sum()),
                             [c[0][0].shape for c in chunks])
        return chunks

    def ragged_warmup_operands(self, n_seqs: int) -> tuple[tuple, int]:
        """Synthetic ragged chunk at bucket ``n_seqs`` with every padded
        doc slot real — warmup compiles the exact (n_seqs, W) dispatch
        shape without caring about content."""
        W, cap = self.max_len, self._ragged_doc_cap
        tok = W // cap
        n_docs = n_seqs * cap
        ids = np.zeros((n_seqs, W), np.int32)
        doc_map = np.repeat(np.arange(n_docs, dtype=np.int32),
                            tok).reshape(n_seqs, cap * tok)
        if cap * tok < W:
            doc_map = np.pad(doc_map, ((0, 0), (0, W - cap * tok)),
                             constant_values=-1)
        pos = np.tile(np.arange(tok, dtype=np.int32), cap)[None, :]
        pos = np.pad(np.repeat(pos, n_seqs, 0),
                     ((0, 0), (0, W - cap * tok)))
        dseq = np.repeat(np.arange(n_seqs, dtype=np.int32), cap)
        doff = np.tile(np.arange(cap, dtype=np.int32) * tok, n_seqs)
        return (ids, doc_map, pos, dseq, doff), n_docs

    def _split_ragged(self, flat):
        """The five arrays of a ragged chunk out of its one buffer
        (``ids``, ``doc_map``, ``pos_ids`` of (n_seqs, W), then
        ``doc_seq``, ``doc_off`` of (n_seqs * doc cap,)): the buffer's
        length names ``n_seqs``, so the offsets are static under jit."""
        W, cap = self.max_len, self._ragged_doc_cap
        n_seqs = flat.shape[0] // (3 * W + 2 * cap)
        rows, n_pad = n_seqs * W, n_seqs * cap
        ids, doc_map, pos_ids = (
            flat[i * rows:(i + 1) * rows].reshape(n_seqs, W)
            for i in range(3))
        return (ids, doc_map, pos_ids, flat[3 * rows:3 * rows + n_pad],
                flat[3 * rows + n_pad:])

    def encode_ragged_chunk(self, args: tuple):
        """The plain ragged forward over one chunk of :meth:`pack_ragged`
        (its ``args``): the five int32 arrays go up as one buffer in one
        explicit transfer. Returns what the forward returns (padded rows,
        or ``(rows, aux)``), the dispatch left asynchronous."""
        import jax.numpy as jnp

        # residency is established EXPLICITLY (jnp.asarray) rather than by
        # letting the jit dispatch transfer its numpy operand implicitly:
        # same bytes over PCIe either way, but the explicit form stays
        # legal under the device sanitizer's steady-state transfer guard
        # (engine/device_sanitizer.py) and under PWT404's discipline
        flat = jnp.asarray(np.concatenate([a.ravel() for a in args]))
        _fr.note_transfers(uploads=1)
        return self._encode_ragged(self.params, flat)

    def encode_batch_device(self, texts: list[str]):
        """Tokenize + encoder forward, returning the (B, hidden) embedding
        still ON DEVICE (a jax array, dispatch left asynchronous). The
        index that embeds text itself (ops/knn.py DeviceEmbeddingKnnIndex)
        hands a query's straight to its scan and scatters a document's
        into the HBM slab — embeddings never visit the host."""
        import jax.numpy as jnp

        from pathway_tpu.engine.profiler import current_profiler

        prof = current_profiler()
        cfg = self.config
        if self.ragged:
            outs = []
            for args, n_docs, _n_pad in self.pack_ragged(texts):
                t0 = _perf_counter() if prof is not None else 0.0
                out = self._embeddings(self.encode_ragged_chunk(args))
                outs.append(out if n_docs == out.shape[0]
                            else _first_rows_fn()(out, n_docs))
                if prof is not None:
                    b, s = args[0].shape  # packed (n_seqs, W) token ids
                    cost = cfg.cost(int(b), int(s), ragged=True)
                    if cost is not None:
                        prof.record_dispatch(
                            *cost, (_perf_counter() - t0) * 1e3)
            return outs[0] if len(outs) == 1 else jnp.concatenate(outs, 0)
        ids, lens = self.pack_tokens(texts)
        t0 = _perf_counter() if prof is not None else 0.0
        # explicit residency, as in :meth:`encode_ragged_chunk`
        out = self._embeddings(self._encode_packed(
            self.params, jnp.asarray(ids), jnp.asarray(lens)))
        _fr.note_transfers(uploads=2)
        if prof is not None:
            b, s = ids.shape
            cost = cfg.cost(int(b), int(s), ragged=False)
            if cost is not None:
                prof.record_dispatch(*cost, (_perf_counter() - t0) * 1e3)
        return out

    def embed_batch(self, texts: list[str]) -> np.ndarray:
        return np.asarray(self.encode_batch_device(texts))

    def __wrapped__(self, texts: list[str], **kwargs) -> list[np.ndarray]:
        # ONE device→host transfer for the whole batch, then zero-copy row
        # views into it (ndarray iteration yields views, never copies) —
        # per-row np.array(...) slicing would re-allocate B×hidden floats
        # per tick on the hot path. The fused on-device ingest
        # (ops/knn.py) bypasses this entirely: embeddings stay in HBM.
        return list(self.embed_batch(list(texts)))

    def get_embedding_dimension(self, **kwargs) -> int:
        return int(self.config.hidden)


class SentenceTransformerEmbedder(BaseEmbedder):
    """Local sentence-transformers model (torch) — reference :268-326.
    Prefer JaxEncoderEmbedder on TPU; this exists for checkpoint parity."""

    def __init__(self, model: str, *, call_kwargs: dict = {},
                 device: str = "cpu", **kwargs):
        kwargs.setdefault("batch", True)
        super().__init__(**kwargs)
        st = _import_or_raise("sentence_transformers",
                              "SentenceTransformerEmbedder")
        self.model = st.SentenceTransformer(model, device=device)
        self.kwargs = call_kwargs

    def __wrapped__(self, texts: list[str], **kwargs) -> list[np.ndarray]:
        out = self.model.encode(list(texts), **{**self.kwargs, **kwargs})
        return [np.asarray(v) for v in out]


class _RemoteEmbedder(BaseEmbedder):
    """Shared shape of the network embedders: async UDF with retry/cache."""

    def __init__(self, *, capacity: int | None = None,
                 retry_strategy: udfs.AsyncRetryStrategy | None = None,
                 cache_strategy: udfs.CacheStrategy | None = None,
                 model: str | None = None, **call_kwargs):
        executor = udfs.async_executor(capacity=capacity,
                                       retry_strategy=retry_strategy)
        super().__init__(executor=executor, cache_strategy=cache_strategy)
        call_kwargs["model"] = model
        self.kwargs = {k: v for k, v in call_kwargs.items() if v is not None}


class OpenAIEmbedder(_RemoteEmbedder):
    """OpenAI /embeddings API (reference embedders.py:83)."""

    def __init__(self, model: str | None = "text-embedding-3-small",
                 api_key: str | None = None, base_url: str | None = None,
                 **kwargs):
        super().__init__(model=model, **kwargs)
        self._client_kwargs = {"api_key": api_key, "base_url": base_url}
        self._client = None

    def _get_client(self):
        if self._client is None:
            openai = _import_or_raise("openai", "OpenAIEmbedder")
            kw = {k: v for k, v in self._client_kwargs.items()
                  if v is not None}
            self._client = openai.AsyncOpenAI(**kw)
        return self._client

    async def __wrapped__(self, input: str, **kwargs) -> np.ndarray:
        resp = await self._get_client().embeddings.create(
            input=[input or "."], **{**self.kwargs, **kwargs})
        return np.array(resp.data[0].embedding)


class LiteLLMEmbedder(_RemoteEmbedder):
    """Any provider through litellm (reference embedders.py:178)."""

    async def __wrapped__(self, input: str, **kwargs) -> np.ndarray:
        litellm = _import_or_raise("litellm", "LiteLLMEmbedder")
        resp = await litellm.aembedding(
            input=[input or "."], **{**self.kwargs, **kwargs})
        return np.array(resp.data[0]["embedding"])


class GeminiEmbedder(_RemoteEmbedder):
    """Google Generative AI embeddings (reference embedders.py:328)."""

    def __init__(self, model: str | None = "models/embedding-001",
                 api_key: str | None = None, **kwargs):
        super().__init__(model=model, **kwargs)
        self._api_key = api_key

    async def __wrapped__(self, input: str, **kwargs) -> np.ndarray:
        genai = _import_or_raise("google.generativeai", "GeminiEmbedder")
        if self._api_key:
            genai.configure(api_key=self._api_key)
        resp = await asyncio.to_thread(
            genai.embed_content, content=input or ".",
            **{**self.kwargs, **kwargs})
        return np.array(resp["embedding"])


class ClipEmbedder(BaseEmbedder):
    """Multimodal embedder over the in-repo CLIP dual encoder
    (models/clip.py) — the TPU-native counterpart of the reference's
    multimodal template (BASELINE config 4: CLIP image+text into one
    index). ``__call__`` embeds text columns; ``image()`` embeds binary
    image columns into the SAME space, so one KNN index serves cross-modal
    retrieval."""

    def __init__(self, *, config=None, params=None, tokenizer=None,
                 seed: int = 0, **kwargs):
        kwargs.setdefault("batch", True)
        kwargs.setdefault("deterministic", True)
        kwargs.setdefault("device", True)  # pipeline via the device bridge
        super().__init__(**kwargs)
        import jax

        from pathway_tpu.models import clip as _clip
        from pathway_tpu.models.tokenizer import HashTokenizer

        self.config = config or _clip.ClipConfig()
        self.params = params if params is not None else \
            _clip.init_clip_params(jax.random.PRNGKey(seed), self.config)
        self.tokenizer = tokenizer or HashTokenizer(
            vocab_size=self.config.text.vocab_size,
            max_len=self.config.text.max_len)
        cfg = self.config
        self._encode_text = jax.jit(
            lambda p, ids, mask: _clip.encode_text(p, ids, mask,
                                                   config=cfg))
        self._encode_image = jax.jit(
            lambda p, px: _clip.encode_image(p, px, config=cfg))
        self._clip = _clip

    _BUCKETS = JaxEncoderEmbedder._BUCKETS

    def embed_text_batch(self, texts: list[str]) -> np.ndarray:
        max_len = self.config.text.max_len
        ids, mask = self.tokenizer.batch(
            [t or "." for t in texts], max_len=max_len)
        # bucket-pad like JaxEncoderEmbedder: varying batch widths would
        # otherwise recompile the jitted text tower per new width
        pad_to = max_len
        for b in self._BUCKETS:
            if ids.shape[1] <= b:
                pad_to = min(b, max_len)
                break
        if ids.shape[1] < pad_to:
            pad = pad_to - ids.shape[1]
            ids = np.pad(ids, ((0, 0), (0, pad)))
            mask = np.pad(mask, ((0, 0), (0, pad)))
        else:
            ids, mask = ids[:, :pad_to], mask[:, :pad_to]
        return np.asarray(self._encode_text(self.params, ids, mask))

    def embed_image_batch(self, images: list) -> np.ndarray:
        px = np.stack([
            self._clip.load_image(im, config=self.config)
            if isinstance(im, bytes) else np.asarray(im, np.float32)
            for im in images
        ])
        return np.asarray(self._encode_image(self.params, px))

    def __wrapped__(self, texts: list[str], **kwargs) -> list[np.ndarray]:
        # zero-copy row views of the single batch transfer
        return list(self.embed_text_batch(list(texts)))

    def image(self):
        """A UDF embedding image bytes/arrays into the shared space."""
        outer = self

        class _ImageUDF(BaseEmbedder):
            def __init__(self):
                super().__init__(batch=True, deterministic=True,
                                 device=True)

            def __wrapped__(self, images: list, **kwargs):
                # zero-copy row views of the single batch transfer
                return list(outer.embed_image_batch(list(images)))

            def get_embedding_dimension(self, **kwargs) -> int:
                return int(outer.config.embed_dim)

        return _ImageUDF()

    def get_embedding_dimension(self, **kwargs) -> int:
        return int(self.config.embed_dim)
