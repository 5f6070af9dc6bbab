"""The quickest proof that the live-RAG path still starts on the chip.

``python chip_smoke.py`` drives the main path once, in ONE process, through
the entry points a user calls:

    files -> pw.io.fs.read(mode="streaming")
          -> VectorStoreServer(embedder=JaxEncoderEmbedder(BGE-small, bf16))
             with default_brute_force_knn_document_index (paged HBM store;
             on one chip the fused DeviceEmbeddingKnnIndex: WordPiece on the
             host, encoder forward + slab scatter as one donated dispatch)
          -> run_server(threaded=True) -> VectorStoreClient over HTTP

at the full published BGE-small width and depth, with seeded random weights
and the synthetic WordPiece vocab (there is no checkpoint and no network on
the chip machine). It checks what comes out (see ``run_smoke``) and prints,
as the last line of stdout, one JSON object naming the device. Any failed
check or exception is a non-zero exit with no such line.

It runs on a TPU only: ``__main__`` passes ``expected_platform="tpu"`` and
no flag or environment variable changes that. The CPU rehearsal of this
same function, at a tiny size, is ``tests/test_chip_smoke.py``. With more
than one chip visible the index is built ``mesh="auto"`` (sharded over the
data axis), otherwise on the one chip with the fused ingest.

It states no speed: seconds printed here say how long set-up (compiles
included) and the request phase took on this run, for the cold/warm
compile-cache comparison — they are not a benchmark.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

# bf16 vs float32 encoder agreement, as the cosine between the two unit
# embeddings of the same text. Why this bound: bfloat16 keeps 8 significant
# bits (relative step 2^-8 ~ 0.4%); over 12 post-LN layers with float32
# accumulation and float32 layernorm the rounding errors add like a random
# walk, not linearly, and the bf16 path also swaps erf-gelu for tanh-gelu
# (<= 3e-3 abs, EncoderConfig.gelu). At the full BGE-small shape on seeded
# random weights that leaves 1 - cos ~ 5e-5 (CPU rehearsal; every run
# prints its own `bf16_vs_f32_min_cos`). 0.999 is 20x that, and still
# fails on any structural fault — a wrong mask, a dropped layer or a
# mis-tiled matmul lands below 0.9.
BF16_VS_F32_MIN_COS = 0.999


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def _make_docs(n: int, max_words: int, seed: int) -> list[str]:
    """Seeded documents of mixed length over the `word{i}` vocabulary the
    synthetic WordPiece vocab holds whole (one token per word): lengths
    skew short with a long tail up to ``max_words``."""
    rng = np.random.default_rng(seed)
    lens = np.clip(rng.geometric(4.0 / max_words, size=n) + 2, 3, max_words)
    return [" ".join(f"word{j}" for j in rng.integers(0, 4096, size=int(k)))
            for k in lens]


def _make_embedder(config, max_len: int, seed: int):
    import jax

    from pathway_tpu.models.encoder import init_params
    from pathway_tpu.models.tokenizer import (WordPieceTokenizer,
                                              make_synthetic_vocab)
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    tokenizer = WordPieceTokenizer(
        make_synthetic_vocab([f"word{i}" for i in range(4096)],
                             vocab_size=config.vocab_size),
        max_len=max_len)
    return JaxEncoderEmbedder(
        config=config, params=init_params(jax.random.PRNGKey(seed), config),
        tokenizer=tokenizer, max_len=max_len)


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_for(pred, timeout_s: float, what: str, poll_s: float = 0.25):
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        try:
            last = pred()
            if last:
                return last
        except OSError:  # server not listening yet / connection refused
            pass
        time.sleep(poll_s)
    raise SmokeFailure(f"timed out after {timeout_s:.0f}s waiting for {what} "
                       f"(last: {last!r})")


def _encoder_agreement(emb, texts: list[str]) -> float:
    """min over ``texts`` of cos(bf16 encode, float32 encode at highest
    matmul precision) — both on the device under test."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import encode

    ids, lens = emb.pack_tokens(texts)
    ids32 = jnp.asarray(ids.astype(np.int32))
    mask = jnp.arange(ids32.shape[1])[None, :] < jnp.asarray(lens)[:, None]
    got = np.asarray(emb.encode_batch_device(texts), dtype=np.float32)
    ref_cfg = dataclasses.replace(emb.config, compute_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(
            lambda p, i, m: encode(p, i, m, config=ref_cfg))(
                emb.params, ids32, mask), dtype=np.float32)
    _check(got.shape == ref.shape == (len(texts), emb.config.hidden)
           and bool(np.isfinite(got).all()),
           f"encoder output finite with shape {got.shape}")
    cos = np.sum(got * ref, axis=1) / (
        np.linalg.norm(got, axis=1) * np.linalg.norm(ref, axis=1))
    return float(cos.min())


def _chunked_scan_check(rows: int, dim: int) -> None:
    """One search against a slab built on the device (as bench_knn does):
    at ``rows`` > ops.knn._CHUNK_ROWS this is the lax.scan-over-chunks
    kernel with its per-chunk lax.top_k and the donated scatter at scale."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.ops.knn import BruteForceKnnIndex, KnnMetric

    index = BruteForceKnnIndex(dim, reserved_space=rows,
                               metric=KnnMetric.COS, dtype="bfloat16")
    chunk = min(1 << 19, rows)
    gen = jax.jit(lambda key: jax.random.uniform(
        key, (chunk, dim), jnp.bfloat16, -1.0, 1.0))
    probe_key, probe_vec = None, None
    for ci, base in enumerate(range(0, rows, chunk)):
        m = min(chunk, rows - base)
        vecs = gen(jax.random.PRNGKey(ci))[:m]
        index.add_batch_device([Pointer(base + i) for i in range(m)], vecs)
        probe_key = Pointer(base + m // 3)
        probe_vec = np.asarray(vecs[m // 3], dtype=np.float32)
    (hits,) = index.search([(Pointer(1 << 40), probe_vec, 5, None)])
    _check(len(index) == rows and len(hits) == 5
           and hits[0][0] == probe_key and abs(hits[0][1]) < 1e-2,
           f"search over a {rows}-row device-built slab returns the probed "
           f"row first (dist {hits[0][1]:.2e})")
    del index


def _decoder_check(cfg, row: int, seed: int, what: str) -> dict:
    """One forward of a decoder whose attention is the blocked core over
    packed rows (models/decoder.py, ops/attention.py ``segment_attention``:
    window and full attention mixed, latent attention, or latent attention
    over an indexer's choice of keys) at ``cfg``'s
    widths on packed rows of ``row`` slots, weights drawn on the device in
    the compute dtype: a long document (longer than the window, where there
    is one) alone in a row, then behind two others. Finite unit embeddings,
    and the document's embedding does not depend on where it lies; on a TPU
    with values of whole lanes the attention took the kernel (of a model
    that chooses its keys: the sparse one)."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import decoder
    from pathway_tpu.ops import attention

    params = jax.jit(lambda key: decoder.init_params(
        key, cfg, cfg.compute_dtype))(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    long_doc = rng.integers(0, cfg.vocab_size, row // 2 + row // 8)
    others = [rng.integers(0, cfg.vocab_size, n)
              for n in (row // 16, row // 4)]

    def packed(docs):
        ids = np.zeros((1, row), np.int32)
        seg = np.full((1, row), -1, np.int32)
        pos = np.zeros((1, row), np.int32)
        last, at = [], 0
        for j, doc in enumerate(docs):
            n = len(doc)
            ids[0, at:at + n], seg[0, at:at + n] = doc, j
            pos[0, at:at + n] = np.arange(n)
            at += n
            last.append(at - 1)
        return ids, seg, pos, np.zeros(len(docs), np.int32), \
            np.asarray(last, np.int32)

    forward = jax.jit(cfg.encode_ragged)
    before = attention.attention_lowerings()
    alone, _ = forward(params, *map(jnp.asarray, packed([long_doc])))
    behind, _ = forward(params, *map(jnp.asarray,
                                     packed(others + [long_doc])))
    took = {k: v - before.get(k, 0)
            for k, v in attention.attention_lowerings().items()}
    alone, behind = np.asarray(alone, np.float32), np.asarray(
        behind, np.float32)
    _check(np.isfinite(alone).all() and np.isfinite(behind).all()
           and np.allclose(np.linalg.norm(behind, axis=1), 1.0, atol=1e-3),
           f"the {what} decoder's embeddings of a {row}-slot row are "
           f"finite unit vectors")
    cos = float(alone[0] @ behind[2])
    _check(cos >= 0.98, f"a document of {len(long_doc)} tokens (window "
           f"{cfg.sliding_window_size}) embeds alike alone and behind two "
           f"others in its row: cos {cos:.5f} >= 0.98")
    lanes = cfg.v_head_dim if cfg.attention_method else cfg.head_dim
    kernel = jax.devices()[0].platform == "tpu" and lanes % 128 == 0
    sparse = "sparse_" if cfg.attention_index else ""
    wanted = sparse + ("kernel" if kernel else "blockwise")
    # the cores' query block follows the heads a key head stacks: a larger
    # one where there is no stacking, which the counter names
    block = attention.block_sizes(row, cfg.attention_rep)[0]
    wide = f"query_block_{block}"
    _check(took[wanted] > 0 and not any(
        n for name, n in took.items() if name not in (wanted, wide))
        and (took.get(wide, 0) > 0) == (block > 256),
           f"attention lowered as the {wanted.replace('_', ' ')} at query "
           f"blocks of {block} ({cfg.attention_rep} query heads a key "
           f"head): {took}")
    del params
    return {"row": row, "layers": cfg.num_hidden_layers,
            "hidden": cfg.hidden_size, "experts": cfg.num_experts,
            "alone_vs_packed_cos": round(cos, 6), "lowerings": took,
            "query_block": block}


def _device_memory(devices) -> list[dict]:
    out = []
    for d in devices:
        ms = d.memory_stats() or {}  # the CPU backend reports none
        out.append({"id": d.id, "bytes_in_use": ms.get("bytes_in_use"),
                    "peak_bytes_in_use": ms.get("peak_bytes_in_use")})
    return out


def _serve_and_check(emb, docs: list[str], *, max_words: int,
                     mesh: str | None, seed: int, request_timeout_s: int,
                     compiles, devices) -> dict:
    """The server phase: build the real graph, ingest ``docs`` from a
    watched directory, add three documents live, answer requests over
    HTTP, then read what the engine says about how it ran."""
    import pathway_tpu as pw
    from pathway_tpu.engine import streaming
    from pathway_tpu.engine.threads import crashed_threads
    from pathway_tpu.internals import autojit
    from pathway_tpu.ops.knn import (BruteForceKnnIndex,
                                     DeviceEmbeddingKnnIndex, KnnMetric)
    from pathway_tpu.parallel.sharded_knn import ShardedKnnIndex
    from pathway_tpu.stdlib.indexing import (
        default_brute_force_knn_document_index)
    from pathway_tpu.xpacks.llm.vector_store import (VectorStoreClient,
                                                     VectorStoreServer)

    n_docs = len(docs)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    docs_dir = os.path.join(workdir, "docs")
    os.makedirs(docs_dir)
    names = [f"doc{i:05d}.txt" for i in range(n_docs)]
    for name, text in zip(names, docs):
        with open(os.path.join(docs_dir, name), "w") as f:
            f.write(text)

    crashes_before = len(crashed_threads())
    try:
        # ---- the real graph, through the entry points a user calls ------
        source = pw.io.fs.read(docs_dir, format="plaintext_by_file",
                               mode="streaming", with_metadata=True)

        def build_index(chunks):
            return default_brute_force_knn_document_index(
                chunks.text, chunks, embedder=emb,
                dimensions=emb.get_embedding_dimension(),
                metadata_column=chunks.metadata, metric=KnnMetric.COS,
                dtype="bfloat16", mesh=mesh)

        server = VectorStoreServer(source, embedder=emb,
                                   index_builder=build_index)
        port = _free_port()
        # with_cache=False: the default DiskCache writes ./Cache under the
        # working directory, and this run leaves nothing in the checkout
        server.run_server(host="127.0.0.1", port=port, threaded=True,
                          with_cache=False)
        client = VectorStoreClient("127.0.0.1", port,
                                   timeout=request_timeout_s)
        n_requests = 0

        def indexed() -> int:
            nonlocal n_requests
            n = client.get_vectorstore_statistics()["file_count"]
            n_requests += 1
            return n

        def top_hits(text: str, k: int = 3) -> list[tuple[str, float]]:
            nonlocal n_requests
            res = client.query(text, k=k)
            n_requests += 1
            return [(os.path.basename(r["metadata"]["path"]), r["dist"])
                    for r in res]

        def top_names(text: str) -> list[str]:
            return [name for name, _dist in top_hits(text)]

        _wait_for(lambda: indexed() == n_docs, request_timeout_s,
                  f"/v1/statistics to report {n_docs} files")
        # one probe per width bucket: each new (1, width) query shape
        # compiles the plain encoder (and k=3 the search kernel) — that is
        # set-up, so the request phase below meets warm shapes only
        by_width: dict[int, int] = {}
        for i, d in enumerate(docs):
            by_width.setdefault(emb.pack_tokens([d])[0].shape[1], i)
            if len(by_width) == len(emb.bucket_widths()):
                break
        probes = [by_width[w] for w in sorted(by_width)]
        _check(len(probes) >= 3,
               f"the corpus spans {len(probes)} width buckets "
               f"{sorted(by_width)}")
        for i in probes:
            _check(top_names(docs[i])[:1] == [names[i]],
                   f"doc {i} ({len(docs[i].split())} words) retrieves "
                   f"itself first")

        # ---- live adds: one tick each, three widths ---------------------
        live = [" ".join(t.split()[:n]) for t, n in zip(
            _make_docs(3, max_words, seed + 1),
            (3, max_words // 3, max_words))]
        live_names = [f"live{j}.txt" for j in range(len(live))]
        for j, (name, text) in enumerate(zip(live_names, live)):
            with open(os.path.join(docs_dir, name), "w") as f:
                f.write(text)
            _wait_for(lambda: indexed() == n_docs + j + 1,
                      request_timeout_s, f"live document {j} to be counted")
            _wait_for(lambda: top_names(text)[:1] == [name],
                      request_timeout_s,
                      f"live document {j} to be its own top hit")
            print(f"  ok: live document {j} ({len(text.split())} words) "
                  "became the top hit for its own text", flush=True)
        t_setup_done = time.perf_counter()

        # ---- the request phase: shapes are warm now ---------------------
        compiles_before = compiles()
        answers = {}
        rng = np.random.default_rng(seed + 2)
        asked = [(names[i], docs[i]) for i in
                 [*probes, *rng.integers(0, n_docs, size=4).tolist()]]
        for name, text in [*asked, *zip(live_names, live)]:
            answers[name] = top_hits(text)
            if answers[name][0][0] != name:
                raise SmokeFailure(
                    f"{name} is not the top hit for its own text: "
                    f"{answers[name]}")
        stats = client.get_vectorstore_statistics()
        n_requests += 1
        serving_s = time.perf_counter() - t_setup_done
        serving_compiles = compiles() - compiles_before
        _check(stats["file_count"] == n_docs + len(live),
               f"/v1/statistics counts {n_docs + len(live)} files")
        # the client raises on any status but 200, so getting here says so
        print(f"  ok: all {n_requests} HTTP requests answered 200 "
              f"({len(answers)} checked self-retrievals)", flush=True)
        if mesh is None:
            _check(serving_compiles == 0,
                   "the request phase compiled nothing after set-up")
        else:
            # on a mesh the query text is embedded through the UDF column,
            # where a completed query's retraction rides the next query's
            # tick: (1, w) and (2, w) encoder shapes both occur, so warm
            # probes do not cover the phase — recorded, not required
            print(f"  note: {serving_compiles} backend compiles in the "
                  "request phase (UDF embedding path)", flush=True)

        # ---- what the engine says about how it ran ----------------------
        (rt,) = streaming.live_runtimes()
        bridge = rt.scheduler.bridge_stats()
        _check(bridge is not None and bridge["legs_resolved"] > 0,
               f"device bridge resolved legs ({bridge})")
        (index,) = [node.op.index for node in rt.runner.graph.nodes
                    if hasattr(node.op, "index")]
        _check(len(index) == n_docs + len(live),
               f"the engine index holds {n_docs + len(live)} rows "
               f"({type(index).__name__})")
        if mesh is None:
            _check(isinstance(index, DeviceEmbeddingKnnIndex)
                   and isinstance(index.inner, BruteForceKnnIndex),
                   "index is the fused DeviceEmbeddingKnnIndex over the "
                   "paged store")
            _check(index.fused_batches > 0 and index.fused_fallbacks == 0
                   and index.inner.upload_rows_total == 0,
                   f"every ingest batch took the fused donated dispatch "
                   f"({index.fused_batches} batches, 0 fallbacks, 0 rows "
                   "through the two-dispatch scatter)")
        else:
            _check(isinstance(index, ShardedKnnIndex),
                   f"mesh={mesh!r} built the ShardedKnnIndex")
        _check(autojit.autojit_stats()["demotions"] == 0,
               "auto-jit demoted nothing")
        new_crashes = crashed_threads()[crashes_before:]
        _check(not new_crashes, f"no engine thread crashed ({new_crashes})")
        # with the index still alive: on a mesh its shards must show up on
        # every device
        memory = _device_memory(devices)
    finally:
        streaming.stop_all()
        shutil.rmtree(workdir, ignore_errors=True)
    return {"t_setup_done": t_setup_done, "serving_s": serving_s,
            "serving_compiles": serving_compiles, "requests": n_requests,
            "answers": answers, "bridge_legs_resolved":
                bridge["legs_resolved"],
            "sample": [docs[i] for i in probes] + live,
            "n_indexed": n_docs + len(live), "memory_serving": memory}


def run_smoke(*, expected_platform: str, config, n_docs: int,
              max_words: int, max_len: int, scan_rows: int,
              mesh: str | None = None, seed: int = 0,
              request_timeout_s: int = 600,
              out_path: str | None = None, decoder_config=None,
              decoder_row: int = 0, latent_config=None,
              latent_row: int = 0, indexed_config=None,
              indexed_row: int = 0) -> dict:
    """Drive the main path once and check it; returns the summary dict
    (also printed). Raises on the first failed check.

    ``expected_platform``: what ``jax.devices()[0].platform`` must be —
    checked before anything else is imported or built. ``config``: a
    callable returning the EncoderConfig (called after the platform check,
    so a refused run builds nothing). ``mesh``: None for the one-chip
    fused index, "auto" to shard the index over every visible device.
    ``scan_rows``: size of the device-built slab of the scan check.
    ``out_path``: where to write the top-k answers as JSON (to compare a
    one-chip with a four-chip run). ``decoder_config``: a callable
    returning a ``DecoderConfig`` of the windowed pattern, forwarded once
    on rows of ``decoder_row`` slots after the server is down (None: no
    such check); ``latent_config``, ``latent_row``: the same for a
    ``DecoderConfig`` of the latent attention pattern; ``indexed_config``,
    ``indexed_row``: of the pattern that chooses its keys."""
    t_start = time.perf_counter()
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"platform={device['platform']} device_kind={device['kind']!r} "
          f"device_count={device['count']}", flush=True)
    if device["platform"] != expected_platform:
        raise SmokeFailure(
            f"JAX runs on {device['platform']!r}, this run needs "
            f"{expected_platform!r} — refusing before building anything")

    import pathway_tpu as pw
    from pathway_tpu.engine.device_sanitizer import install_compile_counter
    from pathway_tpu.engine.profiler import machine_params

    cache_dir = pw.enable_compilation_cache()

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    cache_entries_before = cache_entries()
    print(f"compile_cache_dir={cache_dir} "
          f"entries_at_start={cache_entries_before}", flush=True)
    peaks = machine_params(device["kind"])
    print(f"peaks_for_device_kind={'known' if peaks else 'not in table'}",
          flush=True)
    compiles = install_compile_counter()
    # where set-up time goes, by JAX's own clocks: tracing and lowering are
    # paid per shape even when the persistent cache serves the executable
    jit_seconds = {"jaxpr_trace": 0.0, "jaxpr_to_mlir_module": 0.0,
                   "backend_compile": 0.0}

    def on_jit_event(event: str, duration: float, **_kw) -> None:
        stage = event.rsplit("/", 1)[-1].removesuffix("_duration")
        if event.startswith("/jax/core/compile/") and stage in jit_seconds:
            jit_seconds[stage] += duration

    jax.monitoring.register_event_duration_secs_listener(on_jit_event)
    try:
        cfg = config()
        emb = _make_embedder(cfg, max_len, seed)
        _check(emb.tokenizer.uses_native,
               "native WordPiece in use (built from native/wordpiece.cpp)")
        served = _serve_and_check(
            emb, _make_docs(n_docs, max_words, seed), max_words=max_words,
            mesh=mesh, seed=seed, request_timeout_s=request_timeout_s,
            compiles=compiles, devices=devices)
        # reference agreement + the kernel shapes the server sized down
        min_cos = _encoder_agreement(emb, served["sample"])
        _check(min_cos >= BF16_VS_F32_MIN_COS,
               f"bf16 encoder agrees with float32/highest: min cos "
               f"{min_cos:.6f} >= {BF16_VS_F32_MIN_COS}")
        _chunked_scan_check(scan_rows, cfg.hidden)
        windowed = _decoder_check(
            decoder_config(), decoder_row, seed, "windowed") \
            if decoder_config else None
        latent = _decoder_check(latent_config(), latent_row, seed,
                                "latent") if latent_config else None
        indexed = _decoder_check(indexed_config(), indexed_row, seed,
                                 "indexed") if indexed_config else None
    finally:
        jax.monitoring.unregister_event_duration_listener(on_jit_event)

    answers = served["answers"]
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump({"device": device, "mesh": mesh, "answers": answers},
                      f, indent=1)
    summary = {
        "device": device, "mesh": mesh, "n_docs": served["n_indexed"],
        "encoder": {"layers": cfg.layers, "hidden": cfg.hidden,
                    "heads": cfg.heads, "intermediate": cfg.intermediate,
                    "vocab": cfg.vocab_size, "max_len": max_len},
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": [cache_entries_before, cache_entries()],
        "setup_s": round(served["t_setup_done"] - t_start, 1),
        "serving_s": round(served["serving_s"], 2),
        "total_s": round(time.perf_counter() - t_start, 1),
        "backend_compiles": compiles(),
        "serving_compiles": served["serving_compiles"],
        "jit_seconds": {k: round(v, 1) for k, v in jit_seconds.items()},
        "requests": served["requests"],
        "bridge_legs_resolved": served["bridge_legs_resolved"],
        "bf16_vs_f32_min_cos": round(min_cos, 6),
        "windowed_decoder": windowed,
        "latent_decoder": latent,
        "indexed_decoder": indexed,
        "topk_digest": hashlib.sha256(json.dumps(sorted(
            (q, [name for name, _dist in hits])
            for q, hits in answers.items())).encode()).hexdigest()[:16],
        "memory_serving": served["memory_serving"],
        "memory_end": _device_memory(devices),
    }
    print("summary " + json.dumps(summary), flush=True)
    return summary


def main() -> int:
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out")
    try:
        import jax

        n_devices = len(jax.devices())

        def bge_small():
            from pathway_tpu.models.encoder import EncoderConfig

            return EncoderConfig.bge_small()  # 12 x 384, 12 heads, bf16

        def smallthinker_period():
            """One period of SmallThinker-21BA3B at its published widths:
            benchmark/configs/smallthinker-21b-embed.json."""
            import jax.numpy as jnp

            from pathway_tpu.models.decoder import DecoderConfig

            return DecoderConfig(
                vocab_size=151936, hidden_size=2560, num_hidden_layers=4,
                zero_centred_norm=False, num_attention_heads=28,
                num_key_value_heads=4, head_dim=128,
                partial_rotary_factor=1.0, rope_theta=1.5e6,
                attention_gate=False, qk_norm=False,
                sliding_window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
                sliding_window_size=4096, num_experts=64,
                num_experts_per_tok=6, moe_intermediate_size=768,
                shared_expert_intermediate_size=None, hidden_act="relu",
                router_input="mixer_input", max_len=16384,
                compute_dtype=jnp.bfloat16)

        def longcat_layer():
            """One layer of LongCat-Flash's language model at its published
            widths, 16 of its 512 experts held:
            benchmark/configs/longcat-flash-embed.json (1.24 billion
            parameters a layer, 2.5 GB in bfloat16)."""
            import jax.numpy as jnp

            from pathway_tpu.models.decoder import DecoderConfig

            return DecoderConfig(
                vocab_size=16384, hidden_size=6144, num_hidden_layers=1,
                rms_norm_eps=1e-5, zero_centred_norm=False,
                num_attention_heads=64, rope_theta=1e7,
                attention_method="MLA", q_lora_rank=1536, kv_lora_rank=512,
                qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                ffn_hidden_size=12288, num_experts=512,
                num_experts_per_tok=12, moe_intermediate_size=2048,
                shared_expert_intermediate_size=None, norm_topk_prob=False,
                zero_expert_num=256, routed_scaling_factor=6.0,
                experts_held=(0, 16), max_len=8192,
                compute_dtype=jnp.bfloat16)

        def glm_layers():
            """A dense layer with an indexer and an expert layer that
            shares its choice, of GLM-5.2 at its published widths, 16 of
            its 256 experts held: benchmark/configs/glm-5.2-embed.json
            (1.21 billion parameters, 2.4 GB in bfloat16)."""
            import jax.numpy as jnp

            from pathway_tpu.models.decoder import DecoderConfig

            return DecoderConfig(
                vocab_size=19360, hidden_size=6144, num_hidden_layers=2,
                rms_norm_eps=1e-5, zero_centred_norm=False,
                num_attention_heads=64, rope_theta=8e6,
                attention_method="MLA", q_lora_rank=2048, kv_lora_rank=512,
                qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
                mla_scale_q_lora=False, mla_scale_kv_lora=False,
                ffn_hidden_size=12288, mlp_layer_types=("dense", "sparse"),
                indexer_types=("full", "shared"), index_topk=2048,
                index_n_heads=32, index_head_dim=128, num_experts=256,
                num_experts_per_tok=8, moe_intermediate_size=2048,
                shared_expert_intermediate_size=None, n_shared_experts=1,
                scoring_func="sigmoid", routed_scaling_factor=2.5,
                experts_held=(0, 16), max_len=16384,
                compute_dtype=jnp.bfloat16)

        summary = run_smoke(
            expected_platform="tpu", config=bge_small, n_docs=3000,
            max_words=120, max_len=128, scan_rows=1 << 20,
            mesh="auto" if n_devices > 1 else None,
            out_path=os.path.join(out_dir,
                                  f"chip_smoke_topk_{n_devices}chip.json"),
            decoder_config=smallthinker_period, decoder_row=16384,
            latent_config=longcat_layer, latent_row=8192,
            indexed_config=glm_layers, indexed_row=16384)
    except Exception:  # any failed check or error: exit != 0, no JSON line
        import traceback

        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
