"""Warmup + persistent XLA compilation cache.

The flagship encoder runs under jit with sequence-length bucketing: ~18
distinct (batch, width) shapes (``JaxEncoderEmbedder.bucket_widths``). By
default XLA compiles each shape the first time a serving tick dispatches it
— a ~0.75 s stall per shape *inside* the measured/served window (bench.py
round-5 finding: two in-window compiles cost 1.48 s of a 2.76 s window).

Two fixes, composable:

- ``enable_compilation_cache()`` turns on jax's persistent compilation
  cache, so every shape compiles once per MACHINE instead of once per
  process. The embedders call it, so it is on by default. Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, jax already knows the directory
  and this module sets none; otherwise the cache lives at one fixed path
  inside the checkout (``<repo>/.jax_cache``) — fixed because the path is
  what a later process must find again.
- ``pw.warmup(embedder, index=...)`` eagerly walks the bucket shapes
  (encoder forward, and the fused encode+scatter / search kernels when an
  index is given) so all compilation happens before the first real tick —
  from the persistent cache when warm, from scratch otherwise.

Under ragged batching (``PATHWAY_RAGGED_ENCODER=1`` /
``JaxEncoderEmbedder(ragged=True)``) the compile set is the embedder's
sequence-count buckets (``ragged_buckets()``, ≤ 6 shapes at one fixed
width) instead of the ~18 width buckets — warmup walks those.
"""

from __future__ import annotations

import os
import time as _time
from typing import Any

from pathway_tpu.native.build import _REPO_ROOT

#: The jitted serving entry points whose compile set warmup's ladder
#: covers. This is the bucket registry the PWT4xx static pass audits:
#: PWT407 flags any module/class-level jitted callable with a
#: serving-shaped name that is absent here (its cold compile would land
#: inside the first real query). The perf checker PARSES this literal —
#: never imports the module — so keep it a plain frozenset of string
#: constants. Factory-built kernels (the knn search/scatter closures,
#: autojit bucket programs) are warmed through their owning objects and
#: are not nameable entry points, so they do not appear.
WARMED_ENTRY_POINTS = frozenset({
    "encode_jit",   # models/encoder.py — packed encoder forward
})

_DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache and return the directory
    in use: ``JAX_COMPILATION_CACHE_DIR`` where that is set (jax reads it
    itself, no directory is set in code), else ``<repo>/.jax_cache``.
    Idempotent."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _DEFAULT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every entry: the default thresholds skip sub-second compiles,
    # but 18 x 0.7 s is exactly the stall this exists to delete
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def warmup(embedder: Any = None, *, index: Any = None,
           batch_size: int | None = None, ks: tuple[int, ...] = (),
           autojit_max_bucket: int | None = None) -> dict:
    """Pre-compile the serving-path kernels so no XLA compile lands inside
    a live tick.

    ``embedder``: a :class:`JaxEncoderEmbedder`-shaped object (exposes
    ``bucket_widths()`` / ``_encode_packed`` / ``params``); every bucket
    width is compiled at ``batch_size`` (default: the embedder's
    ``max_batch_size``, else 32). Only the WIDTH dimension is bucketed —
    the batch dimension is whatever the engine dispatches, so the
    no-compile-in-tick guarantee requires pinning it: construct the
    embedder with ``max_batch_size=batch_size`` (as bench.py does) so
    every full dispatch is exactly the warmed shape. Unpinned batch
    sizes still compile on first sight of each new row count.

    ``index``: optionally a device KNN index. A fused
    :class:`DeviceEmbeddingKnnIndex` warms the encode+scatter dispatch at
    every width through scratch slots (removed and flushed afterwards);
    any non-empty index additionally warms its search kernel for each
    fan-out in ``ks``. A non-empty ``ks`` also warms the PLAIN encoder
    next to a fused ingest: text queries
    (``DeviceEmbeddingKnnIndex.search``) dispatch it, and it is a
    separate jit from the fused encode+scatter.

    The persistent compilation cache is switched on first
    (:func:`enable_compilation_cache`), so warmed executables persist
    across processes on this machine.

    Auto-jit (internals/autojit.py): every fused UDF program registered by
    the expression compiler has its power-of-two batch-bucket ladder
    walked (8 up to ``autojit_max_bucket``, default
    ``PATHWAY_AUTO_JIT_WARM_MAX`` or 2048) so the XLA bucket compiles
    happen here instead of inside the first serving ticks. Programs only
    register at graph lowering, so call this AFTER building the runner
    (bench.py's framework leg is the canonical ordering). No-op with
    ``PATHWAY_AUTO_JIT=0``.

    Returns ``{"cache_dir", "compiled", "seconds"}`` where ``compiled``
    lists the (kind, shape) pairs that were walked — auto-jit entries as
    ``("autojit", (program_label, bucket))``.

    Under ``PATHWAY_DEVICE_SANITIZER`` (engine/device_sanitizer.py) this
    call brackets the sanitizer's warmup window: compiles during the walk
    count as warmup, and completion **declares steady state** — from then
    on any backend compile or implicit host→device transfer on a serving
    tick is a :class:`DeviceDisciplineViolation`. Re-warming an armed
    process suspends steady state for the duration instead of violating.
    """
    from pathway_tpu.engine import device_sanitizer as _ds

    _ds.arm()
    with _ds.suspend_steady_state("pw.warmup ladder walk"):
        out = _warmup_impl(embedder, index=index, batch_size=batch_size,
                           ks=ks, autojit_max_bucket=autojit_max_bucket)
    _ds.declare_steady_state()
    return out


def _warmup_impl(embedder: Any = None, *, index: Any = None,
                 batch_size: int | None = None, ks: tuple[int, ...] = (),
                 autojit_max_bucket: int | None = None) -> dict:
    t0 = _time.perf_counter()
    out: dict = {"cache_dir": enable_compilation_cache(), "compiled": []}
    from pathway_tpu.internals.autojit import warm_registered

    out["compiled"].extend(warm_registered(autojit_max_bucket))
    if embedder is None and index is None:
        out["seconds"] = round(_time.perf_counter() - t0, 3)
        return out

    import jax
    import numpy as np

    from pathway_tpu.ops.knn import FusedIngestUnplaceable

    if embedder is None and index is not None:
        embedder = getattr(index, "embedder", None)

    widths: list[int] = []
    if embedder is not None and hasattr(embedder, "bucket_widths"):
        widths = embedder.bucket_widths()
    B = (batch_size or getattr(embedder, "max_batch_size", None) or 32)

    def packed_operands(w: int):
        dtype = np.int16 if getattr(embedder, "_pack_ids", False) \
            else np.int32
        ids = np.zeros((B, w), dtype)
        lens = np.full((B,), max(1, w - 2), np.int32)
        return ids, lens

    fused = getattr(index, "_fused", None)
    inner = getattr(index, "inner", index)
    if embedder is not None and getattr(embedder, "ragged", False):
        # ragged batching: the compile set is the sequence-count buckets
        # (≤ 6 shapes at one fixed width) instead of the ~18 width zoo
        from pathway_tpu.internals.keys import Pointer

        W = getattr(embedder, "max_len", 0)
        for n_seqs in embedder.ragged_buckets():
            ops, n_docs = embedder.ragged_warmup_operands(n_seqs)
            if fused is not None:
                scratch = [Pointer((1 << 62) + i) for i in range(n_docs)]
                try:
                    fused(scratch, embedder.params, *ops, n_rows=n_docs)
                except FusedIngestUnplaceable:
                    jax.block_until_ready(embedder.encode_ragged_chunk(ops))
                    out["compiled"].append(("ragged_encode", (n_seqs, W)))
                    continue
                for k in scratch:
                    inner.remove(k)
                out["compiled"].append(("ragged_fused_ingest", (n_seqs, W)))
                if ks:
                    # same query-path warm as the packed branch: text
                    # queries use the plain ragged encoder (the chunk as
                    # one buffer), not the fused ingest dispatch
                    jax.block_until_ready(embedder.encode_ragged_chunk(ops))
                    out["compiled"].append(("ragged_encode", (n_seqs, W)))
            else:
                jax.block_until_ready(embedder.encode_ragged_chunk(ops))
                out["compiled"].append(("ragged_encode", (n_seqs, W)))
        if fused is not None:
            inner.flush_device()
    elif embedder is not None and widths:
        fused_used = False
        for w in widths:
            ids, lens = packed_operands(w)
            if fused is not None:
                # warm the REAL serving dispatch (encode+scatter is one
                # donated jit, distinct from the plain encoder) through
                # scratch slots, then retract them
                from pathway_tpu.internals.keys import Pointer

                scratch = [Pointer((1 << 62) + i) for i in range(B)]
                try:
                    fused(scratch, embedder.params, ids, lens)
                except FusedIngestUnplaceable:
                    # slab too full for scratch slots: live ingest will
                    # also take the growable two-dispatch fallback
                    # (DeviceEmbeddingKnnIndex.add_batch), so warm the
                    # plain encoder — the dispatch that path uses
                    fused = None
                    jax.block_until_ready(
                        embedder._encode_packed(embedder.params, ids, lens))
                    out["compiled"].append(("encode", (B, w)))
                    continue
                fused_used = True
                for k in scratch:
                    inner.remove(k)
                out["compiled"].append(("fused_ingest", (B, w)))
                if ks:
                    # ``ks`` declares the index serves queries — and TEXT
                    # queries dispatch the PLAIN packed encoder
                    # (DeviceEmbeddingKnnIndex.search), a separate jit
                    # from the fused ingest. Warm it too, or the first
                    # query after steady state compiles in-window (the
                    # device sanitizer caught exactly this gap).
                    jax.block_until_ready(embedder._encode_packed(
                        embedder.params, ids, lens))
                    out["compiled"].append(("encode", (B, w)))
            else:
                jax.block_until_ready(
                    embedder._encode_packed(embedder.params, ids, lens))
                out["compiled"].append(("encode", (B, w)))
        if fused_used:
            # push the scratch removals now (even if a later width fell
            # back): the first live ingest must not compile the plain
            # scatter in-window flushing them
            inner.flush_device()
    if index is not None and ks:
        search_index = inner if hasattr(inner, "_get_search_fn") else None
        if search_index is not None and len(search_index) > 0:
            dim = search_index.dim
            from pathway_tpu.internals.keys import Pointer

            for k in ks:
                search_index.search(
                    [(Pointer((1 << 62)), np.zeros(dim, np.float32), k,
                      None)])
                out["compiled"].append(("search", (k,)))
    out["seconds"] = round(_time.perf_counter() - t0, 3)
    return out
