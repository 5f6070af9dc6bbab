"""TPU-resident brute-force KNN index.

The TPU-native replacement for the reference's BruteForceKNNIndex
(src/external_integration/brute_force_knn_integration.rs:22,187-229 —
ndarray ``index_arr.dot(query_batch)`` + k-smallest on CPU): vectors live in
an HBM-resident padded slab; queries are answered by one jitted
matmul + top-k over the slab (MXU work), with host-side dirty-slot batching
so incremental adds/removes coalesce into few device scatters.

Distance metrics mirror the reference (L2sq / cosine). Sharded multi-chip
variant (slab split over a mesh axis + per-shard top-k + merge) lives in
pathway_tpu/parallel/sharded_knn.py.

Two scale features target the 10M-vector p50 budget (BASELINE.md):

- ``dtype="bfloat16"`` halves slab bytes (10M x 384 = 7.7 GB, fits one
  v5e) AND halves the HBM scan time — the search is bandwidth-bound, so
  latency tracks slab bytes. Scores accumulate in f32 on the MXU
  (``preferred_element_type``), so only storage is low-precision.
- ``dtype="int8"`` halves bytes AGAIN (10M x 384 = 3.8 GB): rows are
  quantized per-row symmetric (scale = max|v|/127) by the on-device
  scatter; the host mirror stays exact float32. int8 values are exactly
  representable in bf16, so the in-kernel bf16 MXU dots with f32
  accumulation are EXACT integer arithmetic — the only precision loss is
  the quantization itself. For cosine the per-row scale cancels
  (cos is row-scale invariant), so the search kernel needs no
  dequantization at all; L2sq folds the scale into the score.
- Above ``_CHUNK_ROWS`` slots the kernel switches to a ``lax.scan`` over
  slab chunks with a per-chunk top-k and a final merge, bounding the
  (B, N) score buffer at (B, chunk) regardless of slab size.

Device storage is PAGED (engine/paged_store.py, Ragged Paged Attention's
memory design): HBM is allocated in page-aligned extents that are never
moved once created, a host page table maps slots to (page, offset), growth
appends an extent, frees return pages to a free list, and the fused
donated ingest grows by allocating pages in one extent, or a fresh extent.
Search runs the same kernels per extent and merges the per-extent top-k,
so the extent layout does not change an answer; an index reserved in one
extent makes one kernel call and no merge.
"""

from __future__ import annotations

import enum
import functools
import math
import time as _time
from typing import Any, Callable

import numpy as np

from pathway_tpu.engine import flight_recorder as _fr
from pathway_tpu.engine.device_bridge import note_ingest_dispatch
from pathway_tpu.engine.profiler import (current_profiler,
                                         ingest_scatter_cost,
                                         knn_search_cost)
from pathway_tpu.internals.keys import Pointer


class KnnMetric(enum.Enum):
    L2SQ = "l2sq"
    COS = "cos"


class FusedIngestUnplaceable(ValueError):
    """The donated one-dispatch ingest cannot take this batch — its rows
    already sit in more than one extent, or no single extent can hold it.
    Raised BEFORE any slot is assigned, so the caller may re-add every
    key through the two-dispatch path, which spans extents. The only
    error that path change may catch: any other ValueError out of the
    fused step (a shape or lowering error) is a bug and must surface."""


_MIN_CAPACITY = 1024
# slabs larger than this are scanned in chunks of this many rows
_CHUNK_ROWS = 1 << 19


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def passes_filter(filter_data: dict, key: Pointer, filt: Any) -> bool:
    """The ONE metadata-filter predicate every index variant dispatches
    through (brute-force, paged, sharded, HNSW): callable filters are
    fail-closed, string filters go through the jmespath-lite engine."""
    data = filter_data.get(key)
    if callable(filt):
        try:
            return bool(filt(data))
        except Exception:
            return False
    from pathway_tpu.internals.jmespath_lite import evaluate_filter

    return evaluate_filter(filt, data)


def planned_capacity(reserved_space: int) -> int:
    """Rows the first extent holds for a reservation — minimum floor,
    128-lane rounding, chunk alignment (the page pool then rounds up to
    whole pages)."""
    cap = max(_MIN_CAPACITY, _round_up(max(reserved_space, 1), 128))
    if cap > _CHUNK_ROWS:
        # the chunked kernel reshapes the slab to (C, chunk, D)
        cap = _round_up(cap, _CHUNK_ROWS)
    return cap


def _np_dtype(dtype: str):
    if dtype == "int8":
        # int8 quantization happens device-side in the scatter; the host
        # mirror stays exact float32 (authoritative for grow/exact reads)
        return np.float32
    if dtype == "float32":
        return np.float32
    if dtype == "bfloat16":
        import ml_dtypes

        return ml_dtypes.bfloat16
    raise ValueError(f"unsupported knn dtype {dtype!r} "
                     "(use 'float32', 'bfloat16' or 'int8')")


def _chunked_search(k: int, score_block, prep_queries):
    """The scan/top-k/merge machinery shared by every search kernel
    variant. ``score_block(q, vectors, extras, valid) -> (B, N) f32``
    scores one slab chunk; ``extras`` is a (possibly empty) tuple of
    per-row (N,) side columns chunked alongside the slab (int8 uses
    (scales, vsq)). Returns a jitted
    ``search(queries, vectors, extras, valid)``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def search(queries, vectors, extras, valid):
        capacity = vectors.shape[0]
        q = prep_queries(queries, vectors)
        if capacity <= _CHUNK_ROWS:
            return jax.lax.top_k(
                score_block(q, vectors, extras, valid), k)
        # scan slab chunks: peak scores buffer is (B, chunk) instead of
        # (B, capacity) — 10M x 384 stays under one chip's HBM
        n_chunks = capacity // _CHUNK_ROWS
        vchunks = vectors.reshape(n_chunks, _CHUNK_ROWS, vectors.shape[1])
        echunks = tuple(e.reshape(n_chunks, _CHUNK_ROWS) for e in extras)
        validc = valid.reshape(n_chunks, _CHUNK_ROWS)

        def body(_, chunk):
            vs, es, val = chunk
            ts, ti = jax.lax.top_k(score_block(q, vs, es, val), k)
            return None, (ts, ti)

        _, (ts, ti) = jax.lax.scan(body, None, (vchunks, echunks, validc))
        # ts/ti: (C, B, k); global slot = chunk_index * _CHUNK_ROWS + ti
        offsets = (jnp.arange(n_chunks,
                              dtype=ti.dtype) * _CHUNK_ROWS)[:, None, None]
        ti = ti + offsets
        cand_s = jnp.moveaxis(ts, 0, 1).reshape(q.shape[0], -1)
        cand_i = jnp.moveaxis(ti, 0, 1).reshape(q.shape[0], -1)
        top_scores, pos = jax.lax.top_k(cand_s, k)
        top_idx = jnp.take_along_axis(cand_i, pos, axis=1)
        return top_scores, top_idx

    return search


@functools.lru_cache(maxsize=None)
def _packed_search_fn(search_fn):
    """``search_fn`` (a :func:`_chunked_search` program) with its two
    results as ONE int32 array ``(B, 2k)``, the scores' bits and then the
    slots, so that both reach the host in one transfer
    (:func:`_unpack_topk`; integers pass through the device untouched).
    Named ``search`` as the program it wraps: the XLA module stays
    ``jit_search``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def search(queries, vectors, extras, valid):
        ts, ti = search_fn(queries, vectors, extras, valid)
        return jnp.concatenate(
            [jax.lax.bitcast_convert_type(ts, jnp.int32), ti], axis=1)

    return search


def _unpack_topk(packed) -> tuple[np.ndarray, np.ndarray]:
    """(scores float32, slots int32) of a :func:`_packed_search_fn`
    result: the one device-to-host transfer of an extent's scan."""
    packed = np.asarray(packed)
    k = packed.shape[1] // 2
    return packed[:, :k].view(np.float32), packed[:, k:]


def _prep_queries(metric: KnnMetric, cast_dtype=None):
    import jax.numpy as jnp

    def prep(queries, vectors):
        if metric == KnnMetric.COS:
            queries = queries / (jnp.linalg.norm(
                queries, axis=1, keepdims=True) + 1e-12)
        return queries.astype(cast_dtype or vectors.dtype)

    return prep


@functools.lru_cache(maxsize=None)
def _shared_search_fn(k: int, metric: KnnMetric):
    """Module-level jitted search kernel, shared by ALL index instances.

    jax.jit caches compiled executables per Python function object —
    per-instance closures would recompile an identical kernel for every
    fresh index (every new pipeline, every test). Capacity, slab dtype
    and chunking are derived from the operand shapes at trace time, so
    one function serves every slab; only (k, metric) must be static.
    """
    import jax
    import jax.numpy as jnp

    def score_block(q, vectors, extras, valid):
        # q (B, D) slab dtype, vectors (N, D) slab dtype → (B, N) f32.
        # MXU takes low-precision inputs but accumulates f32
        # (preferred_element_type) so bf16 storage costs recall, not
        # score arithmetic.
        dots = jax.lax.dot_general(
            q, vectors, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # the self-dot reads the same chunk the q·v dot just loaded, so
        # XLA computes both in one slab pass (measured: removing it does
        # NOT speed the kernel up)
        vn_sq = jax.lax.dot_general(
            vectors, vectors,
            (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        if metric == KnnMetric.COS:
            scores = dots * jax.lax.rsqrt(vn_sq + 1e-12)[None, :]
        else:
            # -||q - v||^2 = 2 q·v - ||v||^2 - ||q||^2 ; drop ||q||^2
            # (constant per query row, does not change ranking)
            scores = 2.0 * dots - vn_sq[None, :]
        return jnp.where(valid[None, :], scores, -jnp.inf)

    return _chunked_search(k, score_block, _prep_queries(metric))


@functools.lru_cache(maxsize=None)
def _shared_search_i8_fn(k: int, metric: KnnMetric):
    """int8-slab search kernel: extras = (scales, vsq) with vsq the
    per-row INT-domain squared norm precomputed by the quantizing
    scatter — no in-kernel self-dot. Slab reads are half the bf16 path's
    bytes; the int8 values convert to bf16 at the MXU operand (exact —
    int8 fits bf16's mantissa), accumulation is f32, so scoring is exact
    arithmetic over the quantized rows."""
    import jax
    import jax.numpy as jnp

    def score_block(q, vectors, extras, valid):
        scales, vsq = extras
        vs = vectors.astype(jnp.bfloat16)
        dots = jax.lax.dot_general(
            q, vs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if metric == KnnMetric.COS:
            # cosine is invariant to per-row scaling: the quantization
            # scale cancels and the INT-domain norm is the right one
            scores = dots * jax.lax.rsqrt(vsq + 1e-12)[None, :]
        else:
            # -||q - v||^2 + ||q||^2 = 2 q·v - ||v||^2 with v = i8 * scale
            scores = (2.0 * dots * scales[None, :]
                      - vsq * (scales * scales)[None, :])
        return jnp.where(valid[None, :], scores, -jnp.inf)

    return _chunked_search(k, score_block,
                           _prep_queries(metric, cast_dtype=jnp.bfloat16))


def _quantize_i8(vals):
    """Per-row symmetric int8 quantization: (q, scale, vsq) with
    scale = max|v|/127 (clamped away from 0) and vsq the INT-domain
    squared row norm. The ONE implementation both the scatter and the
    fused-ingest step trace, so every ingest path quantizes
    bit-identically (grow/re-upload relies on that)."""
    import jax.numpy as jnp

    v = vals.astype(jnp.float32)
    m = jnp.max(jnp.abs(v), axis=1)
    scale = jnp.maximum(m / 127.0, 1e-30)
    q = jnp.clip(jnp.round(v / scale[:, None]), -127, 127).astype(jnp.int8)
    # accumulate in int32 so vsq is exact for any dim up to 2^31 / 127^2
    # (~133k); a float32 accumulator starts rounding partial sums past
    # dim ~1040. The final float32 value rounds at most once.
    qi = q.astype(jnp.int32)
    vsq = jnp.sum(qi * qi, axis=1).astype(jnp.float32)
    return q, scale, vsq


def _quantize_i8_np(vals: np.ndarray):
    """numpy twin of _quantize_i8 (same formula term by term) for indexes
    whose quantization runs host-side before a sharded device_put
    (parallel/sharded_knn.py)."""
    v = vals.astype(np.float32)
    m = np.max(np.abs(v), axis=1)
    scale = np.maximum(m / 127.0, 1e-30).astype(np.float32)
    q = np.clip(np.round(v / scale[:, None]), -127, 127).astype(np.int8)
    # int accumulation, same exactness rationale as _quantize_i8
    qi = q.astype(np.int64)
    vsq = np.sum(qi * qi, axis=1).astype(np.float32)
    return q, scale, vsq


@functools.lru_cache(maxsize=None)
def _shared_scatter_i8_fn():
    """Slab-donating QUANTIZING scatter for int8 indexes (see
    _shared_scatter_fn for the donation rationale)."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def scatter(slab, scales, vsq, valid, idxs, vals, valid_vals):
        q, scale, vn = _quantize_i8(vals)
        return (slab.at[idxs].set(q),
                scales.at[idxs].set(scale),
                vsq.at[idxs].set(vn),
                valid.at[idxs].set(valid_vals))

    return scatter


@functools.lru_cache(maxsize=None)
def _shared_scatter_fn():
    """Module-level jitted slab-DONATING scatter (see _shared_search_fn
    for why module-level): without donation every ``.at[].set``
    materializes a second full slab (15.4 GB transient at 10M bf16 — an
    OOM and a full-HBM copy per call)."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def scatter(slab, valid, idxs, vals, valid_vals):
        return (slab.at[idxs].set(vals.astype(slab.dtype)),
                valid.at[idxs].set(valid_vals))

    return scatter


def _fused_step_fns(producer: Callable, dtype: str):
    """The donated producer+scatter step of a fused ingest, over one
    extent's arrays (shape-polymorphic). ``mode="drop"`` makes the
    out-of-range sentinel slots of ragged padding rows a guaranteed no-op;
    in-range scatters are unaffected."""
    import jax
    import jax.numpy as jnp

    if dtype == "int8":
        @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def step_i8(slab, scales, vsq, valid, slots, *args):
            out, aux = _split_aux(producer(*args))
            q, scale, vn = _quantize_i8(out)
            return (slab.at[slots].set(q, mode="drop"),
                    scales.at[slots].set(scale, mode="drop"),
                    vsq.at[slots].set(vn, mode="drop"),
                    valid.at[slots].set(True, mode="drop")) + aux

        return step_i8

    slab_dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(slab, valid, slots, *args):
        out, aux = _split_aux(producer(*args))
        slab = slab.at[slots].set(out.astype(slab_dtype), mode="drop")
        valid = valid.at[slots].set(True, mode="drop")
        return (slab, valid) + aux

    return step


def _split_aux(out) -> tuple:
    """A producer returns its rows, or ``(rows, aux)``: a small array it
    wants summed outside the step (a routed model's tokens per expert).
    Returns (rows, () or (aux,)): the step hands ``aux`` back beside the
    slab, and the ingest passes it to ``on_aux``."""
    if isinstance(out, tuple):
        return out[0], (out[1],)
    return out, ()


class BruteForceKnnIndex:
    """Incremental exact KNN over device-resident pages of vectors.

    add/remove mutate a host mirror and enqueue dirty slots; search flushes
    pending updates to the device, then runs the jitted scores+top-k kernel
    over every extent and merges the candidates.

    Device memory is a :class:`~pathway_tpu.engine.paged_store.DevicePagePool`
    of page-aligned extents; the host page table (PageAllocator) maps
    slots to (page, offset):

    - **growth is online**: a new extent is appended (established as zeros
      on device); existing extents are never discarded, re-uploaded or
      re-quantized, and the dirty set is untouched — device-authoritative
      rows need no mirror round-trip before growing;
    - **fused donated ingest grows**: new keys allocate pages inside one
      extent (or a fresh extent when none fits the batch) and the donated
      step scatters into that extent only;
    - **frees return pages** to the allocator's free list for reuse —
      ingest/delete churn keeps occupancy bounded;
    - **search is per-extent + merge**: each established extent runs the
      same shared kernel; per-extent top-k candidates merge on the host by
      (score desc, slot asc), so the extent layout never changes an answer
      (an index reserved in one extent makes one kernel call, no merge);
    - ``tenant`` / ``tenant_quotas`` tag this index's pages in the
      allocator and cap them (PageQuotaExceeded past the cap) — the
      accounting unit for many small indexes on one device.

    The host mirror is one contiguous array indexed by global slot (its
    growth is a host-RAM memcpy; device extents are never copied).
    """

    # adds/searches dispatch XLA work: eligible for the scheduler's
    # pipelined device leg (engine/device_bridge.py)
    device_bound = True

    def __init__(self, dimensions: int, *, reserved_space: int = 0,
                 metric: KnnMetric | str = KnnMetric.L2SQ,
                 dtype: str = "float32", page_rows: int | None = None,
                 tenant: Any = None,
                 tenant_quotas: dict[Any, int] | None = None):
        if isinstance(metric, str):
            metric = KnnMetric(metric)
        self.dim = int(dimensions)
        self.metric = metric
        self.dtype = dtype
        self._np_dtype = _np_dtype(dtype)
        self._is_int8 = dtype == "int8"
        # engine lock factory: sanitizable under PATHWAY_LOCK_SANITIZER —
        # this is the lock /metrics threads take for paged-store stats
        # (the PR-7 stats() race class)
        from pathway_tpu.engine.locking import create_rlock

        self._lock = create_rlock("BruteForceKnnIndex._lock")

        self._key_to_slot: dict[Pointer, int] = {}
        self._slot_to_key: dict[int, Pointer] = {}
        self._filter_data: dict[Pointer, Any] = {}
        self._dirty: set[int] = set()    # host → device pending
        self._stale: set[int] = set()    # device → host pending (add_batch_device)
        # rows written to device storage (scatters + dense uploads).
        # upload_rows_total / rows ingested is the re-upload amplification:
        # pages never re-ship, so it stays at 1 through any growth (and at
        # 0 where every batch takes the fused donated dispatch)
        self.upload_rows_total = 0
        self._tenant = tenant
        self._init_storage(reserved_space, page_rows, tenant_quotas)
        # semantic result cache (engine/result_cache.py): fed from the
        # add/remove paths below, filled by the external-index operator.
        # Page geometry comes from storage, so this follows _init_storage.
        from pathway_tpu.engine.result_cache import maybe_result_cache

        self.result_cache = maybe_result_cache(self)
        # page-touch set of the most recent search() — (coverage, fill
        # metadata) the operator pairs with each reply; None until a
        # search ran or when the cache is disabled
        self.last_search_coverage: frozenset | None = None

    def _init_storage(self, reserved_space: int, page_rows: int | None,
                      tenant_quotas: dict[Any, int] | None) -> None:
        from pathway_tpu.engine.paged_store import DevicePagePool

        self._pool = DevicePagePool(
            self.dim, reserved_space=reserved_space,
            rows_per_page=page_rows, tenant_quotas=tenant_quotas,
            lock=self._lock)
        self._host_vectors = np.zeros((self._pool.capacity, self.dim),
                                      dtype=self._np_dtype)
        self._host_valid = np.zeros((self._pool.capacity,), dtype=bool)

    @property
    def capacity(self) -> int:
        return self._pool.capacity

    def page_stats(self) -> dict:
        with self._lock:
            return self._pool.stats()

    # ------------------------------------------------------------------
    # slot allocation through the page table
    # ------------------------------------------------------------------
    def _ensure_free(self, n: int) -> None:
        """Guarantee ``n`` subsequent ``_take_slot`` calls succeed."""
        self._pool.ensure_free(n, self._tenant)
        self._extend_mirror()

    def _take_slot(self) -> int:
        return self._pool.allocator.take_slot(self._tenant)

    def reserve_rows(self, n: int) -> None:
        """Pre-size storage for ``n`` upcoming adds (used by the snapshot
        restore path): one right-sized extent instead of the doubling
        cascade, so a bulk re-establish uploads into fewer extents. Lock
        taken here — call before add_batch."""
        with self._lock:
            self._pool.reserve_rows(n, self._tenant)
            self._extend_mirror()

    def _grow(self, min_rows: int = 0) -> None:
        """One more extent of at least ``min_rows`` rows. Host-side only
        until the next flush: the device extents are untouched (no
        re-upload, dirty set unchanged, device-authoritative rows stay
        put)."""
        self._pool.grow(min_rows=min_rows)
        self._extend_mirror()

    def _extend_mirror(self) -> None:
        """Track pool capacity in the host mirror."""
        cap = self._pool.capacity
        old = self._host_vectors.shape[0]
        if cap <= old:
            return
        new_vec = np.zeros((cap, self.dim), dtype=self._np_dtype)
        new_vec[:old] = self._host_vectors
        self._host_vectors = new_vec
        new_valid = np.zeros((cap,), dtype=bool)
        new_valid[:old] = self._host_valid
        self._host_valid = new_valid

    def _assign_slots(self, keys: list[Pointer],
                      take: Callable | None = None) -> np.ndarray:
        """Slot per key (existing or freshly taken). Lock held; room for
        the new keys has already been ensured — ``take`` must not fail.
        The fused ingest passes a ``take`` pinned to one extent."""
        slots = np.empty(len(keys), dtype=np.int32)
        k2s, s2k = self._key_to_slot, self._slot_to_key
        take = take or self._take_slot
        for i, key in enumerate(keys):
            slot = k2s.get(key)
            if slot is None:
                slot = take()
                k2s[key] = slot
                s2k[slot] = key
            slots[i] = slot
        return slots

    # ------------------------------------------------------------------
    # operator-state snapshots (engine/persistence.py): capture the host
    # view — key map, synced mirror rows, filter payloads — so a restart
    # rebuilds the device extents by re-upload, never by re-embedding
    # ------------------------------------------------------------------
    def snapshot_state(self) -> dict:
        with self._lock:
            # device-authoritative rows (fused/device adds) land in the
            # mirror first: the mirror is exact float32 for every dtype
            # (int8 quantization happens device-side at scatter)
            self._sync_mirror()
            keys = list(self._key_to_slot)
            if keys:
                slots = np.fromiter((self._key_to_slot[k] for k in keys),
                                    np.int64, len(keys))
                vectors = self._host_vectors[slots].copy()
            else:
                vectors = np.zeros((0, self.dim), dtype=self._np_dtype)
            return {"dim": self.dim, "dtype": self.dtype, "keys": keys,
                    "vectors": vectors,
                    "filter_data": dict(self._filter_data)}

    def restore_state(self, state: dict) -> None:
        if int(state["dim"]) != self.dim or state["dtype"] != self.dtype:
            raise ValueError(
                f"snapshot carries a ({state['dim']}, {state['dtype']}) "
                f"index but this run built ({self.dim}, {self.dtype}) — "
                "the pipeline changed between runs")
        keys = list(state["keys"])
        if not keys:
            return
        self.reserve_rows(len(keys))
        self.add_batch(keys, np.asarray(state["vectors"],
                                        dtype=self._np_dtype))
        fd = state["filter_data"]
        if fd:
            fks = list(fd)
            self.set_filter_data(fks, [fd[k] for k in fks])

    # ------------------------------------------------------------------
    # maintenance (called from the external-index operator on data diffs)
    # ------------------------------------------------------------------
    def add(self, key: Pointer, vector: Any, filter_data: Any | None = None) -> None:
        with self._lock:
            vec = np.asarray(vector, dtype=self._np_dtype).reshape(-1)
            if vec.shape[0] != self.dim:
                raise ValueError(
                    f"vector dim {vec.shape[0]} != index dim {self.dim}")
            if key not in self._key_to_slot:
                self._ensure_free(1)
            slot = int(self._assign_slots([key])[0])
            self._host_vectors[slot] = vec
            self._host_valid[slot] = True
            if filter_data is not None:
                self._filter_data[key] = filter_data
            self._dirty.add(slot)
            self._stale.discard(slot)  # host write wins
            if self.result_cache is not None:
                self.result_cache.on_insert(slot, key, vec)

    def set_filter_data(self, keys: list[Pointer],
                        filter_data: list[Any] | None) -> None:
        """Record per-key metadata-filter payloads (None entries skipped).
        The single write path for every add variant — incl. the fused
        text ingest, which updates the store without a vector call."""
        if filter_data is None:
            return
        if len(filter_data) != len(keys):
            raise ValueError(
                f"{len(keys)} keys but {len(filter_data)} filter_data entries")
        with self._lock:
            fd = self._filter_data
            for key, data in zip(keys, filter_data):
                if data is not None:
                    fd[key] = data

    def add_batch(self, keys: list[Pointer], vectors,
                  filter_data: list[Any] | None = None) -> None:
        """Vectorized add: one mirror write for a whole batch of rows."""
        if len(keys) == 0:
            return
        vecs = np.asarray(vectors, dtype=self._np_dtype)
        if vecs.ndim != 2 or vecs.shape[1] != self.dim:
            raise ValueError(
                f"expected ({len(keys)}, {self.dim}) vectors, got {vecs.shape}")
        if vecs.shape[0] != len(keys):
            raise ValueError(
                f"{len(keys)} keys but {vecs.shape[0]} vectors")
        self.set_filter_data(keys, filter_data)
        with self._lock:
            n_new = len({k for k in keys if k not in self._key_to_slot})
            self._ensure_free(n_new)
            slots = self._assign_slots(keys)
            self._host_vectors[slots] = vecs
            self._host_valid[slots] = True
            slot_list = slots.tolist()
            self._dirty.update(slot_list)
            self._stale.difference_update(slot_list)  # host write wins
            if self.result_cache is not None:
                self.result_cache.on_insert_batch(slots, keys, vecs)

    def add_batch_device(self, keys: list[Pointer], vectors,
                         filter_data: list[Any] | None = None) -> None:
        """Device-to-device add: ``vectors`` is a jax (n, dim) array already
        resident on the chip (e.g. fresh encoder output). The extents are
        updated by an on-device scatter and the host mirror is marked stale
        (synced lazily, only when a host-side read needs it) — embeddings
        never round-trip through the host (~1.5 KB/doc of download+upload
        kept off the hot ingest path)."""
        if len(keys) == 0:
            return
        import jax.numpy as jnp

        if vectors.ndim != 2 or vectors.shape[1] != self.dim or \
                vectors.shape[0] != len(keys):
            raise ValueError(
                f"expected ({len(keys)}, {self.dim}) device vectors, got "
                f"{vectors.shape}")
        self.set_filter_data(keys, filter_data)
        with self._lock:
            n_new = len({k for k in keys if k not in self._key_to_slot})
            self._ensure_free(n_new)
            slots = self._assign_slots(keys)
            self._flush_to_device()  # pending host rows first: this write wins
            self._scatter(slots, vectors, jnp.ones(len(keys), dtype=bool))
            self._host_valid[slots] = True
            slot_list = slots.tolist()
            self._stale.update(slot_list)
            self._dirty.difference_update(slot_list)  # device write wins
            if self.result_cache is not None:
                # vectors are device-resident — no host beat test possible,
                # and the uncovered-page rule dooms every entry anyway
                self.result_cache.invalidate_all()

    def make_fused_ingest(self, producer: Callable,
                          on_aux: Callable | None = None):
        """Fuse a producer (e.g. the encoder forward pass) with the
        scatter into ONE jitted dispatch, donating the extent so XLA
        updates it in place (no copy, no extra dispatch, nothing returns to
        the host). This is the hot embed+index path: the reference runs
        embedder UDF → index.add per row on the CPU
        (xpacks/llm/embedders.py + brute_force_knn_integration.rs); here
        the embedding tensor never leaves the chip.

        ``producer(*args) -> (n, dim) array``, or ``(array, aux)``: ``aux``
        comes back from the step still on the device and goes to
        ``on_aux``. Returns
        ``ingest(keys, *args, n_rows=None)``; ``n_rows`` is the producer's
        output row count when it exceeds ``len(keys)`` (ragged-packed
        batches pad their doc dimension) — padding rows scatter to an
        out-of-range sentinel slot and are dropped.

        One donated step scatters into ONE extent: new keys allocate pages
        in the extent with the most room, or in a fresh extent sized for
        the batch (FusedIngestUnplaceable where no single extent can take
        it).
        """
        step = _fused_step_fns(producer, self.dtype)

        def ingest(keys: list[Pointer], *args,
                   n_rows: int | None = None) -> None:
            with self._lock:
                aux = self._fused_ingest(step, keys, args, n_rows)
                if self.result_cache is not None:
                    # donated device scatter: same rule as add_batch_device
                    self.result_cache.invalidate_all()
            if aux and on_aux is not None:
                on_aux(aux[0])

        return ingest

    @staticmethod
    def _pad_slots(slots: np.ndarray, n_rows: int | None, sentinel: int):
        import jax.numpy as jnp

        if n_rows is not None and n_rows > len(slots):
            # ragged batches pad the producer's doc dimension: sentinel
            # (out-of-range) slots + the steps' mode="drop" scatters
            # discard the padding rows
            slots = np.concatenate([
                slots,
                np.full(n_rows - len(slots), sentinel, np.int32)])
        return jnp.asarray(slots)

    def _fused_ingest(self, step, keys: list[Pointer], args,
                      n_rows: int | None) -> list:
        from pathway_tpu.engine.paged_store import PageQuotaExceeded

        alloc = self._pool.allocator
        n_new = len({k for k in keys if k not in self._key_to_slot})
        ext_ids = {self._pool.extent_index_of(self._key_to_slot[k])
                   for k in keys if k in self._key_to_slot}
        if len(ext_ids) > 1:
            # a batch updating rows already spread across extents takes
            # the two-dispatch fallback (DeviceEmbeddingKnnIndex catches
            # this)
            raise FusedIngestUnplaceable(
                "fused ingest cannot update rows spanning multiple "
                "extents in one donated step")
        capped = alloc.quota_capped_slots(self._tenant)
        if capped is not None and capped < n_new:
            raise PageQuotaExceeded(
                f"tenant {self._tenant!r} needs {n_new} slots but its "
                f"page quota caps it at {capped} more")
        if ext_ids:
            eidx = next(iter(ext_ids))
        else:
            eidx = max(range(len(self._pool.extents)),
                       key=lambda e: alloc.free_slots_available(
                           self._tenant, regions=[e]))
            if alloc.free_slots_available(
                    self._tenant, regions=[eidx]) < n_new:
                # ONLINE GROWTH under donation: a fresh extent sized for
                # the batch — the previously donated extents are untouched
                self._grow(min_rows=n_new)
                eidx = len(self._pool.extents) - 1
        if alloc.free_slots_available(self._tenant, regions=[eidx]) < n_new:
            # the one extent cannot hold the batch (updated rows pin it,
            # or the tenant's quota caps it below the batch even after a
            # grow): take the two-dispatch fallback, which allocates
            # across extents — checked BEFORE any slot is assigned, so a
            # failed fused attempt never leaks phantom key mappings
            raise FusedIngestUnplaceable(
                "fused ingest cannot place this batch in one extent")
        self._flush_to_device()
        ext = self._pool.extents[eidx]
        self._establish_extent(ext)
        slots = self._assign_slots(
            keys, take=lambda: alloc.take_slot(self._tenant,
                                               regions=[eidx]))
        dev_slots = self._pad_slots(slots - ext.base, n_rows, ext.rows)
        if self._is_int8:
            (ext.vectors, ext.scales, ext.vsq, ext.valid, *aux) = step(
                ext.vectors, ext.scales, ext.vsq, ext.valid,
                dev_slots, *args)
        else:
            ext.vectors, ext.valid, *aux = step(
                ext.vectors, ext.valid, dev_slots, *args)
        self._host_valid[slots] = True
        slot_list = slots.tolist()
        self._stale.update(slot_list)
        self._dirty.difference_update(slot_list)
        return aux

    def _sync_mirror(self) -> None:
        """Pull device-authoritative rows back into the host mirror (lock
        held). Needed before host-side exact reads and snapshots."""
        if not self._stale:
            return
        idxs = np.fromiter(self._stale, dtype=np.int64)
        self._stale.clear()
        for ext, local, pos in self._pool.split_by_extent(idxs):
            if not ext.established:
                continue
            rows_global = idxs[pos]
            local = local.astype(np.int32)
            # a consolidation read at a mirror boundary (host exact reads,
            # snapshots), amortized over the whole stale set
            rows = np.asarray(ext.vectors[local])
            if self._is_int8:
                scales = np.asarray(ext.scales[local], dtype=np.float32)
                rows = rows.astype(np.float32) * scales[:, None]
            self._host_vectors[rows_global] = rows.astype(self._np_dtype)

    def remove(self, key: Pointer) -> None:
        with self._lock:
            slot = self._key_to_slot.pop(key, None)
            if slot is None:
                return
            del self._slot_to_key[slot]
            self._filter_data.pop(key, None)
            self._host_valid[slot] = False
            self._pool.allocator.release_slot(slot)
            self._dirty.add(slot)
            self._stale.discard(slot)
            if self.result_cache is not None:
                self.result_cache.on_delete(slot, key)

    def __len__(self) -> int:
        return len(self._key_to_slot)

    # ------------------------------------------------------------------
    # device sync + search, per extent
    # ------------------------------------------------------------------
    def _slab_itemsize(self) -> int:
        """Bytes per element of the DEVICE rows (the host mirror may be
        wider: int8 keeps an exact f32 mirror)."""
        if self._is_int8:
            return 1
        return 2 if self.dtype == "bfloat16" else 4

    def _establish_extent(self, ext) -> None:
        """Zero device arrays for one extent (on-device allocation, no
        host transfer) — rows arrive by scatter only, so establishment is
        one-time and extents are never re-created."""
        if ext.established:
            return
        import jax.numpy as jnp

        if self._is_int8:
            ext.vectors = jnp.zeros((ext.rows, self.dim), dtype=jnp.int8)
            # per-row quantization scale + INT-domain squared norm
            ext.scales = jnp.zeros((ext.rows,), jnp.float32)
            ext.vsq = jnp.zeros((ext.rows,), jnp.float32)
        else:
            slab_dtype = (jnp.bfloat16 if self.dtype == "bfloat16"
                          else jnp.float32)
            ext.vectors = jnp.zeros((ext.rows, self.dim), dtype=slab_dtype)
        ext.valid = jnp.zeros((ext.rows,), dtype=bool)

    def _scatter(self, idxs: np.ndarray, vals, valid_vals):
        """Extent-donating scatter of global slots ``idxs`` through the
        shared jitted kernels, one dispatch per extent touched."""
        import jax.numpy as jnp

        self.upload_rows_total += len(idxs)
        prof = current_profiler()
        t0 = _time.perf_counter() if prof is not None else 0.0
        groups = list(self._pool.split_by_extent(idxs))
        for ext, local, pos in groups:
            self._establish_extent(ext)
            if len(groups) == 1:
                vsub, valsub = vals, valid_vals
            else:
                vsub, valsub = vals[pos], valid_vals[pos]
            if self._is_int8:
                (ext.vectors, ext.scales, ext.vsq,
                 ext.valid) = _shared_scatter_i8_fn()(
                    ext.vectors, ext.scales, ext.vsq, ext.valid,
                    jnp.asarray(local, dtype=jnp.int32), vsub, valsub)
            else:
                ext.vectors, ext.valid = _shared_scatter_fn()(
                    ext.vectors, ext.valid,
                    jnp.asarray(local, dtype=jnp.int32), vsub, valsub)
        if prof is not None:
            flops, nbytes = ingest_scatter_cost(
                len(idxs), self.dim, itemsize=self._slab_itemsize())
            prof.record_dispatch("ingest_scatter", flops, nbytes,
                                 (_time.perf_counter() - t0) * 1e3)

    def _flush_to_device(self):
        import jax.numpy as jnp

        if not self._dirty:
            return
        idxs = np.fromiter(self._dirty, dtype=np.int64)
        self._dirty.clear()
        scatter_rows: list[np.ndarray] = []
        for ext, local, pos in self._pool.split_by_extent(idxs):
            if not ext.established and not self._is_int8 \
                    and len(pos) * 2 >= ext.rows:
                # bulk load of a fresh extent: one dense upload of its
                # mirror range — rows outside the dirty set are zeros with
                # valid False. int8 always scatters: quantization happens
                # in the scatter kernel
                ext.vectors = jnp.asarray(
                    self._host_vectors[ext.base:ext.base + ext.rows])
                ext.valid = jnp.asarray(
                    self._host_valid[ext.base:ext.base + ext.rows])
                self.upload_rows_total += ext.rows
            else:
                scatter_rows.append(idxs[pos])
        if scatter_rows:
            rows = np.concatenate(scatter_rows)
            self._scatter(rows, jnp.asarray(self._host_vectors[rows]),
                          jnp.asarray(self._host_valid[rows]))

    def flush_device(self) -> None:
        """Push pending host-mirror changes to the device now (async
        dispatch). Bulk loaders call this per ingest chunk so transfers
        overlap the next chunk's host-side work instead of serializing
        into one giant blocking upload at first search."""
        with self._lock:
            self._flush_to_device()

    def drain(self) -> None:
        """Block until every dispatched scatter/ingest resolved — benches
        stamp sustained throughput after this."""
        import jax

        with self._lock:
            # pwt-ok: PWT402 — deliberate barrier: drain() exists to
            # block until dispatched device work resolves
            jax.block_until_ready(
                [(ext.vectors, ext.valid) for ext in self._pool.extents
                 if ext.established])

    def _get_search_fn(self, k: int):
        """Jitted search(queries, vectors, extras, valid) — pair with
        ``_extent_extras(ext)`` at the call site."""
        if self._is_int8:
            return _shared_search_i8_fn(k, self.metric)
        return _shared_search_fn(k, self.metric)

    def _extent_extras(self, ext) -> tuple:
        """Per-row side columns the search kernel needs next to the rows
        ((scales, vsq) for int8, () otherwise)."""
        if self._is_int8:
            return (ext.scales, ext.vsq)
        return ()

    @staticmethod
    def _extent_fetch_cap(ext) -> int:
        """The chunked kernel's per-chunk top-k bounds one extent's
        candidate fetch at the chunk size."""
        return min(ext.rows, _CHUNK_ROWS)

    def _device_topk(self, qmat, fetch_k: int):
        """(scores, global slot ids) as host arrays, exactly ``fetch_k``
        columns, best first. Lock held, device state flushed. While a
        flight recorder is on this is the span ``search.scan``: first
        search program dispatched -> last result fetched."""
        prof = current_profiler()
        # [established extents scanned, seconds until their jitted calls
        # returned], filled by the parts loop
        scan = [0, 0.0] if _fr.recording() else None
        if prof is None and scan is None:
            return self._device_topk_parts(qmat, fetch_k)
        t0 = _time.perf_counter()
        out = self._device_topk_parts(qmat, fetch_k, scan)
        if scan is not None:
            _fr.live_span("search.scan", t0, _time.perf_counter(),
                          queries=int(qmat.shape[0]), fetch_k=fetch_k,
                          extents=scan[0], dispatch_ms=scan[1] * 1e3)
        if prof is not None:
            # the per-extent kernels scan exactly the established rows
            # (the parts loop fetches every extent's result, so the wall
            # is honest device time); cost the scan over those rows, not
            # the pool's capacity
            rows = sum(e.rows for e in self._pool.extents if e.established)
            if rows:
                flops, nbytes = knn_search_cost(
                    int(qmat.shape[0]), rows, self.dim,
                    itemsize=self._slab_itemsize(),
                    extra_row_bytes=8 if self._is_int8 else 0)
                prof.record_dispatch("knn_search", flops, nbytes,
                                     (_time.perf_counter() - t0) * 1e3)
        return out

    def _device_topk_parts(self, qmat, fetch_k: int,
                           scan: list | None = None):
        # every extent's scan is dispatched before any result is waited
        # for, and an extent's scores and slots come back as one array
        found = []
        for ext in self._pool.extents:
            if not ext.established:
                continue  # never written → no valid rows to score
            k_e = min(fetch_k, self._extent_fetch_cap(ext))
            fn = _packed_search_fn(self._get_search_fn(k_e))
            if scan is not None:
                t0 = _time.perf_counter()
            found.append((ext.base, fn(
                qmat, ext.vectors, self._extent_extras(ext), ext.valid)))
            if scan is not None:
                scan[0] += 1
                scan[1] += _time.perf_counter() - t0
        if len(found) > 1:
            for _base, packed in found:
                packed.copy_to_host_async()
        _fr.note_transfers(fetches=len(found))
        parts = []
        for base, packed in found:
            ts, ti = _unpack_topk(packed)
            parts.append((ts, ti + base))
        if not parts:
            B = int(qmat.shape[0])
            return (np.full((B, fetch_k), -np.inf, np.float32),
                    np.zeros((B, fetch_k), np.int64))
        if len(parts) == 1 and parts[0][0].shape[1] == fetch_k:
            return parts[0]
        # merge per-extent candidates: stable argsort on descending score
        # reproduces top_k's tie order (candidates are laid out in global
        # slot order: extents by base, top_k ties by ascending local slot)
        cand_s = np.concatenate([p[0] for p in parts], axis=1)
        cand_i = np.concatenate([p[1] for p in parts], axis=1)
        order = np.argsort(-cand_s, axis=1, kind="stable")[:, :fetch_k]
        top_s = np.take_along_axis(cand_s, order, axis=1)
        top_i = np.take_along_axis(cand_i, order, axis=1)
        if top_s.shape[1] < fetch_k:
            # capacity counts not-yet-established extents, so the
            # established candidates can undershoot an escalated fetch_k —
            # pad to the contract width (-inf rows read as exhausted)
            pad = fetch_k - top_s.shape[1]
            top_s = np.pad(top_s, ((0, 0), (0, pad)),
                           constant_values=-np.inf)
            top_i = np.pad(top_i, ((0, 0), (0, pad)))
        return top_s, top_i

    def search(self, queries: list[tuple]) -> list[tuple]:
        """Batched search: [(qkey, vector, limit, filter)] →
        per query a tuple of (match_key, score) pairs, best first.
        Scores follow the reference convention: L2sq distance (lower=better,
        reported as distance) or cosine distance 1-cos_sim.

        While a flight recorder is on, the call is the span
        ``index.search`` with the counts :meth:`_search` hands up, and
        ``uploads`` and ``fetches``: the transfers made for the queries
        (their matrix up, an extent's result down; a flush of pending
        rows is ``flush_rows``'s to tell)."""
        if not queries:
            return []
        if not _fr.recording():
            return self._search(queries, None)
        counts: dict = {}
        t0 = _time.perf_counter()
        with _fr.counting_transfers(counts):
            out = self._search(queries, counts)
        _fr.live_span("index.search", t0, _time.perf_counter(),
                      queries=len(queries), **counts)
        return out

    def _search(self, queries: list[tuple], counts: dict | None,
                qmat=None) -> list[tuple]:
        """:meth:`search` of one or more queries. ``qmat``: the queries'
        vectors as a device array ``(len(queries), dim)``, which goes to
        the scan as it is (cast to float32 on the device where it is not,
        which is exact) while ``q[1]`` is not read; None: the matrix is
        stacked from the host vectors ``q[1]`` and uploaded. The ranking
        loop fetches a device matrix only where a branch needs a query's
        vector on the host (an L2 distance, the exhaustive filtered pass).

        A ``counts`` dict is filled with what the stages outside the scan
        amounted to: ``flush_rows`` (pending rows the flush wrote),
        ``prepare_ms`` (lock taken -> the query matrix handed to the
        device), ``rank_ms`` (slot -> key, filter, distance; every round)
        and ``rounds`` (scans: one without a selective filter). None: no
        clock is read."""
        timed = counts is not None
        if self._tenant is not None:
            # per-tenant serving metrics: the query keys ARE the engine
            # keys the request tracker registered at enqueue, so this is
            # where tenant identity meets the request span
            from pathway_tpu.engine.request_tracker import live_trackers

            for trk in live_trackers():
                trk.attribute_tenant((q[0] for q in queries), self._tenant)
        with self._lock:
            if not self._key_to_slot:
                # empty-index scan touches nothing: an entry filled from
                # it covers no pages, so ANY later insert invalidates it
                if self.result_cache is not None:
                    self.last_search_coverage = frozenset()
                return [() for _ in queries]
            if timed:
                t_prepare = _time.perf_counter()
                counts.update(flush_rows=len(self._dirty), rank_ms=0.0,
                              rounds=0)
            self._flush_to_device()
            if self.result_cache is not None:
                # coverage AFTER the flush — it must describe exactly the
                # device state the kernels below scan: the established
                # extents (the ISSUE-19 page-touch contract)
                self.last_search_coverage = self._pool.touched_page_ids()
            import jax.numpy as jnp

            max_k = max(int(q[2] or 3) for q in queries)
            # over-fetch when filters present so post-filtering still fills
            # k; the chunked kernel's per-chunk top-k bounds fetch at the
            # chunk size
            has_filter = any(q[3] is not None for q in queries)
            fetch_cap = min(self.capacity, _CHUNK_ROWS)
            fetch_k = min(fetch_cap,
                          max_k * 4 if has_filter else max_k)
            fetch_k = max(fetch_k, 1)
            host_q = None

            def host_rows():
                """The queries' vectors on the host, a row a query: the
                caller's, or the device matrix fetched when first asked."""
                nonlocal host_q
                if host_q is None:
                    # pwt-ok: PWT402 — reached by an L2 distance and the
                    # exhaustive filtered pass alone; the cosine path of
                    # a text query never asks
                    host_q = np.asarray(qmat)
                    _fr.note_transfers(fetches=1)
                return host_q

            if qmat is None:
                host_q = np.stack([np.asarray(q[1], dtype=np.float32)
                                   .reshape(-1) for q in queries])
                qmat = jnp.asarray(host_q)
                _fr.note_transfers(uploads=1)
            elif qmat.dtype != jnp.float32:
                qmat = qmat.astype(jnp.float32)
            if timed:
                counts["prepare_ms"] = (_time.perf_counter()
                                        - t_prepare) * 1e3

            while True:
                top_scores, top_idx = self._device_topk(qmat, fetch_k)
                if timed:
                    t_rank = _time.perf_counter()

                out = []
                exhausted = True
                for qi, (qkey, _qvec, limit, filt) in enumerate(queries):
                    limit = int(limit or 3)
                    matches = []
                    qnorm_sq = None
                    ranks_seen = 0
                    for rank in range(fetch_k):
                        score = top_scores[qi, rank]
                        if not math.isfinite(score):
                            break
                        ranks_seen += 1
                        slot = int(top_idx[qi, rank])
                        key = self._slot_to_key.get(slot)
                        if key is None:
                            continue
                        if filt is not None and not self._passes_filter(key,
                                                                        filt):
                            continue
                        if self.metric == KnnMetric.COS:
                            dist = 1.0 - float(score)
                        else:
                            if qnorm_sq is None:
                                q = host_rows()[qi]
                                qnorm_sq = float(q @ q)
                            dist = max(0.0, qnorm_sq - float(score))
                        matches.append((key, dist))
                        if len(matches) >= limit:
                            break
                    if len(matches) < limit and ranks_seen == fetch_k:
                        # a selective filter ate the whole candidate list
                        # and more live slots remain: escalate the fetch
                        exhausted = False
                    out.append(tuple(matches))
                if timed:
                    counts["rank_ms"] += (_time.perf_counter()
                                          - t_rank) * 1e3
                    counts["rounds"] += 1
                if exhausted or not has_filter:
                    return out
                if fetch_k >= fetch_cap:
                    # the chunked kernel caps per-chunk top-k at the chunk
                    # size; a filter so selective that it eats that many
                    # top candidates falls back to an exact host-side pass
                    # over the mirror — completeness over speed in the
                    # pathological case
                    return [
                        r if len(r) >= int(q[2] or 3) or q[3] is None
                        else self._exhaustive_filtered_search(
                            host_rows()[qi], int(q[2] or 3), q[3])
                        for qi, (q, r) in enumerate(zip(queries, out))
                    ]
                fetch_k = min(fetch_cap, fetch_k * 4)

    def _exhaustive_filtered_search(self, qvec, limit: int, filt):
        """Exact filtered top-k over the host mirror (lock held)."""
        self._sync_mirror()
        keys = [k for k in self._key_to_slot
                if self._passes_filter(k, filt)]
        if not keys:
            return ()
        slots = np.fromiter((self._key_to_slot[k] for k in keys),
                            dtype=np.int64)
        vecs = self._host_vectors[slots].astype(np.float32)
        q = np.asarray(qvec, dtype=np.float32).reshape(-1)
        if self.metric == KnnMetric.COS:
            qn = q / (np.linalg.norm(q) + 1e-12)
            vn = vecs / (np.linalg.norm(vecs, axis=1, keepdims=True) + 1e-12)
            dists = 1.0 - vn @ qn
        else:
            dists = np.sum((vecs - q[None, :]) ** 2, axis=1)
        order = np.argsort(dists, kind="stable")[:limit]
        return tuple((keys[int(i)], float(dists[int(i)])) for i in order)

    def latency_probe(self, *, batch_size: int = 1, k: int = 10,
                      reps: int = 32, seed: int = 0) -> float:
        """Device execution time per search batch, in ms.

        Runs ``reps`` full searches inside ONE jitted ``fori_loop`` dispatch
        (distinct resident queries each iteration, results folded into a
        carry so nothing dead-code-eliminates) and divides the wall time.
        This isolates the kernel from per-dispatch host overhead, so the
        <20 ms p50 target (BASELINE.md) can be read against the kernel +
        HBM scan alone.
        """
        import time as _time

        import jax
        import jax.numpy as jnp

        with self._lock:
            if not self._key_to_slot:
                raise ValueError("empty index")
            self._flush_to_device()
            run, operands = self._probe_searcher(k)
            rng = np.random.default_rng(seed)
            qpool = jnp.asarray(rng.random(
                (reps, batch_size, self.dim), dtype=np.float32) * 2.0 - 1.0)

            @jax.jit
            def probe(qpool, operands):
                def body(i, acc):
                    ts, ti = run(qpool[i], operands)
                    return acc + jnp.sum(ts) + jnp.sum(ti).astype(jnp.float32)

                return jax.lax.fori_loop(0, reps, body, jnp.float32(0.0))

            float(probe(qpool, operands))  # compile + warm
            t0 = _time.perf_counter()
            float(probe(qpool, operands))
            total = _time.perf_counter() - t0
            return total / reps * 1e3

    def _probe_searcher(self, k: int):
        """``(run, operands)`` with ``run(qbatch, operands) -> (ts, ti)``
        jit-traceable — the device side of one search over the extents as
        they stand, so latency_probe measures the real storage layout."""
        import jax
        import jax.numpy as jnp

        exts = [e for e in self._pool.extents if e.established]
        fns = [self._get_search_fn(min(k, self._extent_fetch_cap(e)))
               for e in exts]
        bases = [e.base for e in exts]
        operands = tuple((e.vectors, self._extent_extras(e), e.valid)
                         for e in exts)

        def run(q, operands):
            ts_all, ti_all = [], []
            for fn, base, (vectors, extras, valid) in zip(
                    fns, bases, operands):
                ts, ti = fn(q, vectors, extras, valid)
                ts_all.append(ts)
                ti_all.append(ti + base)
            if len(ts_all) == 1:
                return ts_all[0], ti_all[0]
            cand_s = jnp.concatenate(ts_all, axis=1)
            cand_i = jnp.concatenate(ti_all, axis=1)
            ms, pos = jax.lax.top_k(cand_s, min(k, cand_s.shape[1]))
            return ms, jnp.take_along_axis(cand_i, pos, axis=1)

        return run, operands

    def _passes_filter(self, key: Pointer, filt: Any) -> bool:
        return passes_filter(self._filter_data, key, filt)


# one class, two names: scripts that import the index by its store's name
# keep working
PagedKnnIndex = BruteForceKnnIndex


class DeviceEmbeddingKnnIndex:
    """External index whose add/search take raw TEXT: tokenization runs on
    the host (C++ WordPiece), the encoder forward runs on device, and the
    fresh embeddings scatter straight into the HBM slab — they never visit
    the host. This is the TPU-native "embedder inside the index" layout:
    the reference embeds through a Python UDF column and hands host
    ndarrays to the index (xpacks/llm/vector_store.py:214-292 +
    brute_force_knn_integration.rs), paying a device→host→device round
    trip per document that this path deletes. Both dispatches (encode,
    scatter) are asynchronous, so the next engine batch's host work
    overlaps device compute. A query's embedding never visits the host
    either: the encoder's output is the scan's operand, and a text search
    makes one upload (the packed tokens) and one fetch (scores and slots).

    ``embedder`` must expose ``encode_batch_device(texts) -> (B, dim)``
    jax array (JaxEncoderEmbedder does).
    """

    device_bound = True

    def __init__(self, embedder, inner: BruteForceKnnIndex):
        self.embedder = embedder
        self.inner = inner
        # encode + scatter as ONE donated dispatch (make_fused_ingest):
        # the embedding never becomes a separate device buffer handed
        # from one jit to the next, and the slab updates in place
        self._fused = None
        # ingest batches by path: one donated dispatch each, or — where
        # the fused dispatch could not place the batch
        # (FusedIngestUnplaceable) — the two-dispatch path
        self.fused_batches = 0
        self.fused_fallbacks = 0
        self._ragged = bool(getattr(embedder, "ragged", False))
        on_aux = getattr(embedder, "note_producer_aux", None)
        if self._ragged and hasattr(embedder, "ragged_device_producer"):
            self._fused = inner.make_fused_ingest(
                embedder.ragged_device_producer, on_aux)
        elif hasattr(embedder, "pack_tokens") and \
                hasattr(embedder, "device_producer"):
            self._fused = inner.make_fused_ingest(embedder.device_producer,
                                                  on_aux)

    def add_batch(self, keys: list[Pointer], texts,
                  filter_data: list[Any] | None = None) -> None:
        """While a flight recorder is on, the call is the span
        ``index.add_batch``: ``docs``, the encoder ``dispatches`` of the
        path it took, and whether that was the ``fused`` one."""
        spans = _fr.recording()
        if spans:
            t0 = _time.perf_counter()
        dispatches, fused = self._add_batch(keys, texts, filter_data, spans)
        if spans:
            _fr.live_span("index.add_batch", t0, _time.perf_counter(),
                          docs=len(keys), dispatches=dispatches,
                          fused=fused)

    def _add_batch(self, keys: list[Pointer], texts,
                   filter_data: list[Any] | None,
                   spans: bool) -> tuple[int, int]:
        """(encoder dispatches, 1 where they were the fused ones)."""
        texts = [str(t) for t in texts]
        # (token slots, real tokens, documents) of each fixed-shape chunk
        # the ragged packer made of this batch
        held: list[tuple[int, int, int]] = []
        if self._fused is not None:
            try:
                if self._ragged:
                    # ragged-packed fused ingest: one donated dispatch per
                    # fixed-shape chunk; padded doc rows scatter-drop
                    d0 = 0
                    work = getattr(self.embedder, "dispatch_work", None)
                    chunks = self.embedder.pack_ragged(texts)
                    # args[1] is the packed rows' document map
                    held = [(args[0].size, int(np.count_nonzero(
                        args[1] >= 0)), n_docs)
                        for args, n_docs, _n_pad in chunks]
                    for (args, n_docs, n_pad), (slots, tokens, _n) in zip(
                            chunks, held):
                        if spans:
                            t0 = _time.perf_counter()
                        self._fused(keys[d0:d0 + n_docs],
                                    self.embedder.params, *args,
                                    n_rows=n_pad)
                        if spans:
                            t1 = _time.perf_counter()
                        # the ingest budget's floor is the documents that
                        # fill a dispatch (engine/qos.py): this is ingest
                        # for certain, which the embedder cannot know
                        note_ingest_dispatch(slots, tokens, n_docs)
                        # what attention had to do there, where the model
                        # has such layers (summed for /metrics)
                        attn = work(args) if work is not None else {}
                        if spans:
                            _fr.live_span(
                                "embedder.dispatch", t0, t1, docs=n_docs,
                                rows=int(args[0].shape[0]), tokens=tokens,
                                **attn)
                        d0 += n_docs
                else:
                    ids, lens = self.embedder.pack_tokens(texts)
                    self._fused(keys, self.embedder.params, ids, lens)
                self.inner.set_filter_data(keys, filter_data)
                self.fused_batches += 1
                return len(held) or 1, 1
            except FusedIngestUnplaceable:
                # batch spans extents / fits no single one — fall through
                # to the two-dispatch path (re-adds every key, so a
                # partially-fused ragged batch stays consistent)
                self.fused_fallbacks += 1
        # ``encode_batch_device`` packs the same chunks again and makes a
        # dispatch of each
        for slots, tokens, n_docs in held:
            note_ingest_dispatch(slots, tokens, n_docs)
        vecs = self.embedder.encode_batch_device(texts)
        self.inner.add_batch_device(keys, vecs, filter_data)
        return len(held) or 1, 0

    def add(self, key: Pointer, text, filter_data: Any | None = None) -> None:
        self.add_batch([key], [text],
                       None if filter_data is None else [filter_data])

    def remove(self, key: Pointer) -> None:
        self.inner.remove(key)

    def flush_device(self) -> None:
        # forwarded so the external-index operator's ingest-only-tick
        # flush (engine/index_ops.py) reaches the wrapped store
        self.inner.flush_device()

    def drain(self) -> None:
        self.inner.drain()

    def __len__(self) -> int:
        return len(self.inner)

    def search(self, queries: list[tuple]) -> list[tuple]:
        """While a flight recorder is on, the call is the span
        ``index.search`` (the inner index's counts on it, and not a second
        span of its own) holding ``search.embed``: tokenize, pack, the
        one upload and the encoder's dispatch. The embeddings stay on the
        device and are the scan's operand."""
        if not queries:
            return []
        texts = [str(q[1]) for q in queries]
        if not _fr.recording():
            return self.inner._search(
                queries, None, self.embedder.encode_batch_device(texts))
        counts: dict = {}
        t0 = _time.perf_counter()
        with _fr.counting_transfers(counts):
            qmat = self.embedder.encode_batch_device(texts)
            _fr.live_span("search.embed", t0, _time.perf_counter(),
                          queries=len(queries))
            out = self.inner._search(queries, counts, qmat)
        _fr.live_span("index.search", t0, _time.perf_counter(),
                      queries=len(queries), **counts)
        return out
