"""Multi-chip sharded brute-force KNN.

Pod-scale variant of ops/knn.py (reference: BruteForceKNNIndex,
src/external_integration/brute_force_knn_integration.rs:22,187-229 — which
is per-worker: each timely worker owns the rows routed to it by key shard).
Here the vector slab is a list of extents, each one logical array of shape
``(n_shards, cap_per_shard, dim)`` sharded over the mesh ``data`` axis:
each chip scores queries against its local block of every extent (one MXU
matmul each), takes a local top-k, and the per-shard candidates are merged
with a second top-k — the cross-chip traffic is only ``n_shards × B × k``
scores over ICI, never the slab itself. This is the distributed-KNN design
for BASELINE.md config 5 (multi-worker KNN over a stream).
"""

from __future__ import annotations

import math
from typing import Any, Callable

import numpy as np

from pathway_tpu.internals.keys import Pointer
from pathway_tpu.ops.knn import KnnMetric, _quantize_i8_np, _round_up
from pathway_tpu.ops.knn import passes_filter as _passes
from pathway_tpu.parallel.mesh import DATA_AXIS, get_mesh
from pathway_tpu.parallel.mesh import shard_map as _shard_map


def slab_cap_per_shard(n_shards: int, reserved_space: int,
                       page_rows: int) -> int:
    """Per-shard slab capacity for a reservation of ``reserved_space`` rows.

    The ONE place the slab layout is decided: the index constructor sizes
    its first extent with it and the static shard checker
    (internals/static_check/shard_check.py, PWT102) predicts padding/skew
    from it — the two can never disagree about what a reservation costs.
    Each shard's slab is a whole number of pages, so the per-shard capacity
    rounds up to the page size.
    """
    per = max(reserved_space // n_shards + 1, 1)
    return _round_up(max(128, _round_up(per, 128)), page_rows)


def pages_per_shard(n_shards: int, reserved_space: int,
                    page_rows: int) -> int:
    """What a reservation costs in PAGES per shard — the unit the allocator
    and the static checker (PWT111) both reason in."""
    return slab_cap_per_shard(n_shards, reserved_space,
                              page_rows) // page_rows


def search_operand_layout(dtype: str) -> tuple[tuple[tuple, int], ...]:
    """``((sharded_axes, rank), ...)`` per search-kernel operand, in call
    order: queries, then one extent's vectors, valid (+ scales, vsq for
    int8). ``sharded_axes`` is a tuple of mesh axis names, one per leading
    operand dim (empty = replicated) — the symbolic twin of the
    ``in_specs`` handed to ``shard_map``. Shared by ``_get_search_fn`` and
    the static shard checker (PWT103), so the spec/rank contract is
    asserted against the layout the kernel actually uses."""
    base = (
        ((), 2),            # queries (B, D): replicated
        ((DATA_AXIS,), 3),  # vectors (S, C, D): slab dim over the data axis
        ((DATA_AXIS,), 2),  # valid (S, C)
    )
    if dtype == "int8":
        base = base + (
            ((DATA_AXIS,), 2),  # scales (S, C)
            ((DATA_AXIS,), 2),  # vsq (S, C)
        )
    return base


class _ShardExtent:
    """One sharded device allocation: ``cap_per_shard`` rows PER SHARD,
    laid out as (n_shards, cap_per_shard, dim) over the mesh data axis.
    Global slots [base + s*cap, base + (s+1)*cap) belong to shard s."""

    __slots__ = ("base", "cap_per_shard", "vectors", "valid", "scales",
                 "vsq")

    def __init__(self, base: int, cap_per_shard: int):
        self.base = base
        self.cap_per_shard = cap_per_shard
        self.vectors = None
        self.valid = None
        self.scales = None
        self.vsq = None



class ShardedKnnIndex:
    """Exact KNN over mesh-sharded extents of vectors.

    Each shard's slab is a whole number of pages (``slab_cap_per_shard``
    page-aligned ⇒ ``pages_per_shard`` is the reservation unit) tracked by
    ONE PageAllocator whose regions are (extent, shard) blocks. Adds are
    balanced by always allocating from the emptiest shard (the reference
    balances by key-hash routing, src/engine/dataflow/shard.rs:6-20;
    explicit balancing avoids hash skew in the slab). Growth appends a
    sharded extent — a fresh (S, C_new, D) device allocation — with no slot
    remapping and no re-upload of existing extents. The search kernel
    scores every extent shard-locally, merges the per-extent top-k on-chip,
    and only then pays the cross-chip all-gather: ICI traffic stays
    n_shards x B x k scores regardless of extent count."""

    device_bound = True  # pipeline through the device bridge (graph.py)

    def __init__(self, dimensions: int, *, mesh=None,
                 reserved_space: int = 0,
                 metric: KnnMetric | str = KnnMetric.L2SQ,
                 dtype: str = "float32", page_rows: int | None = None,
                 tenant: Any = None,
                 tenant_quotas: dict[Any, int] | None = None):
        if isinstance(metric, str):
            metric = KnnMetric(metric)
        if dtype not in ("float32", "bfloat16", "int8"):
            raise ValueError(f"unsupported sharded knn dtype {dtype!r} "
                             "(use 'float32', 'bfloat16' or 'int8')")
        self.dim = int(dimensions)
        self.metric = metric
        # per-shard slab storage: bf16 halves slab bytes/scan time per
        # chip, int8 halves them again (host-side per-row quantization at
        # flush, same scheme as ops/knn.py _quantize_i8; the host mirror
        # stays exact f32)
        self.dtype = dtype
        self._mesh = mesh if mesh is not None else get_mesh()
        self.n_shards = int(self._mesh.shape[DATA_AXIS])
        from pathway_tpu.engine.locking import create_rlock

        self._lock = create_rlock("ShardedKnnIndex._lock")
        self._key_to_slot: dict[Pointer, int] = {}
        self._slot_to_key: dict[int, Pointer] = {}
        self._filter_data: dict[Pointer, Any] = {}
        self._dirty: set[int] = set()
        self._search_fn_cache: dict[tuple, Callable] = {}
        self._tenant = tenant
        self._init_storage(reserved_space, page_rows, tenant_quotas)

    def _init_storage(self, reserved_space: int, page_rows: int | None,
                      tenant_quotas: dict[Any, int] | None) -> None:
        from pathway_tpu.engine.paged_store import (PageAllocator,
                                                    quota_pages,
                                                    register_pool)
        from pathway_tpu.engine.paged_store import page_rows as _page_rows

        self._page_rows = _page_rows(page_rows)
        quota_p = ({t: quota_pages(rows, self._page_rows)
                    for t, rows in tenant_quotas.items()}
                   if tenant_quotas else None)
        self.cap_per_shard = 0  # grows as extents are added
        self._extents: list[_ShardExtent] = []
        self._allocator = PageAllocator(self._page_rows, quota_p)
        # per-shard free-row counters: the emptiest-shard choice runs per
        # KEY on bulk ingest, and a full allocator scan there is O(S*E)
        # dict work per row. The counters are exact without quotas; with
        # quotas the allocator scan stays authoritative (quota headroom is
        # global, a raw counter could overstate a shard's availability)
        self._shard_free_rows = [0] * self.n_shards
        self.grow_events = 0
        self._host_vectors = np.zeros((0, self.dim), dtype=np.float32)
        self._host_valid = np.zeros((0,), dtype=bool)
        self._add_extent(slab_cap_per_shard(
            self.n_shards, reserved_space, self._page_rows))
        register_pool(self)

    @property
    def total_capacity(self) -> int:
        return self.n_shards * self.cap_per_shard

    def __len__(self) -> int:
        return len(self._key_to_slot)

    def stats(self) -> dict:
        """Pool-stats shape for engine.paged_store.live_paged_stats."""
        return self.page_stats()

    def page_stats(self) -> dict:
        with self._lock:
            st = self._allocator.stats()
            st.update({
                "capacity_rows": self.total_capacity,
                "extents": len(self._extents),
                "grow_events": self.grow_events,
                "shards": self.n_shards,
            })
            return st

    # -- extents ---------------------------------------------------------
    def _add_extent(self, cap_per_shard: int) -> None:
        base = self.total_capacity
        ext = _ShardExtent(base, cap_per_shard)
        eidx = len(self._extents)
        self._extents.append(ext)
        for s in range(self.n_shards):
            self._allocator.add_region(
                (eidx, s), base + s * cap_per_shard,
                cap_per_shard // self._page_rows)
            self._shard_free_rows[s] += cap_per_shard
        self.cap_per_shard += cap_per_shard
        cap = self.total_capacity
        new_vec = np.zeros((cap, self.dim), dtype=np.float32)
        new_vec[:len(self._host_vectors)] = self._host_vectors
        self._host_vectors = new_vec
        new_valid = np.zeros((cap,), dtype=bool)
        new_valid[:len(self._host_valid)] = self._host_valid
        self._host_valid = new_valid

    def _grow(self) -> None:
        """Online growth: one more sharded extent (per-shard size doubles
        the per-shard total so far) — existing extents, slot ids and the
        dirty set are untouched."""
        self.grow_events += 1
        self._add_extent(_round_up(self.cap_per_shard, self._page_rows))

    # -- slot allocation through per-shard page regions ------------------
    def _shard_regions(self, shard: int) -> list:
        return [(e, shard) for e in range(len(self._extents))]

    def _shard_of(self, slot: int) -> int:
        for ext in self._extents:
            if slot < ext.base + self.n_shards * ext.cap_per_shard:
                return (slot - ext.base) // ext.cap_per_shard
        raise IndexError(slot)

    def _shard_free(self, shard: int) -> int:
        if self._allocator.tenant_quota_pages is None:
            return self._shard_free_rows[shard]
        return self._allocator.free_slots_available(
            self._tenant, regions=self._shard_regions(shard))

    def _ensure_free(self, n: int) -> None:
        from pathway_tpu.engine.paged_store import PageQuotaExceeded

        capped = self._allocator.quota_capped_slots(self._tenant)
        if capped is not None and capped < n:
            # growth cannot help: the tenant's quota, not the pool, is
            # the limit (and an unguarded loop would grow forever)
            raise PageQuotaExceeded(
                f"tenant {self._tenant!r} needs {n} slots but its page "
                f"quota caps it at {capped} more")
        while self._allocator.free_slots_available(self._tenant) < n:
            self._grow()

    def _release_slot(self, slot: int) -> None:
        self._allocator.release_slot(slot)
        self._shard_free_rows[self._shard_of(slot)] += 1

    def _alloc_slot(self, key: Pointer) -> int:
        """Slot for ``key``, allocating from the emptiest shard (growing
        if all shards are full). Lock held."""
        slot = self._key_to_slot.get(key)
        if slot is None:
            shard = max(range(self.n_shards), key=self._shard_free)
            if self._shard_free(shard) == 0:
                self._ensure_free(1)
                shard = max(range(self.n_shards), key=self._shard_free)
            slot = self._allocator.take_slot(
                self._tenant, regions=self._shard_regions(shard))
            self._shard_free_rows[shard] -= 1
            self._key_to_slot[key] = slot
            self._slot_to_key[slot] = key
        return slot

    # ------------------------------------------------------------------
    def add(self, key: Pointer, vector: Any,
            filter_data: Any | None = None) -> None:
        with self._lock:
            vec = np.asarray(vector, dtype=np.float32).reshape(-1)
            if vec.shape[0] != self.dim:
                raise ValueError(
                    f"vector dim {vec.shape[0]} != index dim {self.dim}")
            slot = self._alloc_slot(key)
            self._host_vectors[slot] = vec
            self._host_valid[slot] = True
            if filter_data is not None:
                self._filter_data[key] = filter_data
            self._dirty.add(slot)

    def add_batch(self, keys: list[Pointer], vectors,
                  filter_data: list[Any] | None = None) -> None:
        """Vectorized add (same contract as ops.knn add_batch); rows go to
        the emptiest shards."""
        if len(keys) == 0:
            return
        vecs = np.asarray(vectors, dtype=np.float32)
        if vecs.ndim != 2 or vecs.shape[1] != self.dim:
            raise ValueError(
                f"expected ({len(keys)}, {self.dim}) vectors, got {vecs.shape}")
        if vecs.shape[0] != len(keys):
            raise ValueError(
                f"{len(keys)} keys but {vecs.shape[0]} vectors")
        if filter_data is not None and len(filter_data) != len(keys):
            raise ValueError(
                f"{len(keys)} keys but {len(filter_data)} filter_data entries")
        with self._lock:
            n_new = len({k for k in keys if k not in self._key_to_slot})
            self._ensure_free(n_new)
            slots = np.empty(len(keys), dtype=np.int64)
            for i, key in enumerate(keys):
                slots[i] = self._alloc_slot(key)
                if filter_data is not None and filter_data[i] is not None:
                    self._filter_data[key] = filter_data[i]
            self._host_vectors[slots] = vecs
            self._host_valid[slots] = True
            self._dirty.update(slots.tolist())

    def remove(self, key: Pointer) -> None:
        with self._lock:
            slot = self._key_to_slot.pop(key, None)
            if slot is None:
                return
            del self._slot_to_key[slot]
            self._filter_data.pop(key, None)
            self._host_valid[slot] = False
            self._release_slot(slot)
            self._dirty.add(slot)

    # -- device sync per extent ------------------------------------------
    def flush_device(self) -> None:
        """Push pending host-mirror changes to the sharded device extents
        now (same contract as ops.knn.BruteForceKnnIndex.flush_device — the
        external-index operator calls this after ingest-only ticks so
        uploads ride the device leg instead of the next query)."""
        with self._lock:
            self._flush_to_device()

    def _sharding(self):
        import jax

        return jax.sharding.NamedSharding(
            self._mesh, jax.sharding.PartitionSpec(DATA_AXIS))

    def _zeros_sharded(self, shape, dtype):
        """Zero-establish a sharded array ON DEVICE when the runtime
        supports out_shardings (no host transfer); host zeros upload as
        the fallback."""
        import jax
        import jax.numpy as jnp

        sharding = self._sharding()
        try:
            return jax.jit(lambda: jnp.zeros(shape, dtype),
                           out_shardings=sharding)()
        except TypeError:
            return jax.device_put(np.zeros(shape, dtype), sharding)

    def _establish_extent(self, ext: _ShardExtent) -> None:
        if ext.vectors is not None:
            return
        import jax.numpy as jnp

        S, C, D = self.n_shards, ext.cap_per_shard, self.dim
        if self.dtype == "int8":
            ext.vectors = self._zeros_sharded((S, C, D), jnp.int8)
            # per-row scale + INT-domain squared norm, both (S, C) f32
            ext.scales = self._zeros_sharded((S, C), jnp.float32)
            ext.vsq = self._zeros_sharded((S, C), jnp.float32)
        else:
            dt = jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32
            ext.vectors = self._zeros_sharded((S, C, D), dt)
        ext.valid = self._zeros_sharded((S, C), jnp.bool_)

    def _split_by_extent(self, idxs: np.ndarray):
        for ext in self._extents:
            span = self.n_shards * ext.cap_per_shard
            in_ext = (idxs >= ext.base) & (idxs < ext.base + span)
            if not in_ext.any():
                continue
            pos = np.flatnonzero(in_ext)
            yield ext, idxs[pos] - ext.base, pos

    def _flush_to_device(self):
        import jax.numpy as jnp

        for ext in self._extents:
            self._establish_extent(ext)
        if not self._dirty:
            return
        idxs = np.fromiter(self._dirty, dtype=np.int64)
        self._dirty.clear()
        for ext, local, pos in self._split_by_extent(idxs):
            rows_global = idxs[pos]
            sh, sl = local // ext.cap_per_shard, local % ext.cap_per_shard
            if self.dtype == "int8":
                q, scale, vsq = _quantize_i8_np(
                    self._host_vectors[rows_global])
                ext.vectors = ext.vectors.at[sh, sl].set(jnp.asarray(q))
                ext.scales = ext.scales.at[sh, sl].set(jnp.asarray(scale))
                ext.vsq = ext.vsq.at[sh, sl].set(jnp.asarray(vsq))
            else:
                rows = self._host_vectors[rows_global]
                if self.dtype == "bfloat16":
                    import ml_dtypes

                    rows = rows.astype(ml_dtypes.bfloat16)
                ext.vectors = ext.vectors.at[sh, sl].set(jnp.asarray(rows))
            ext.valid = ext.valid.at[sh, sl].set(
                jnp.asarray(self._host_valid[rows_global]))

    # -- multi-extent search ---------------------------------------------
    @staticmethod
    def _local_scores(queries, vecs, valid_row, extras, metric, int8):
        """(B, C) scores of replicated queries vs one shard-local slab
        block of one extent."""
        import jax
        import jax.numpy as jnp

        if int8:
            scales, vsq = extras
            vs = vecs.astype(jnp.bfloat16)
            if metric == KnnMetric.COS:
                qn = queries / (jnp.linalg.norm(
                    queries, axis=1, keepdims=True) + 1e-12)
                dots = jax.lax.dot_general(
                    qn.astype(jnp.bfloat16), vs,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                # per-row scale cancels for cosine (see ops/knn.py)
                scores = dots * jax.lax.rsqrt(vsq + 1e-12)[None, :]
            else:
                dots = jax.lax.dot_general(
                    queries.astype(jnp.bfloat16), vs,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                scores = (2.0 * dots * scales[None, :]
                          - vsq * (scales * scales)[None, :])
        elif metric == KnnMetric.COS:
            qn = queries / (jnp.linalg.norm(queries, axis=1,
                                            keepdims=True) + 1e-12)
            vn = vecs / (jnp.linalg.norm(
                vecs.astype(jnp.float32), axis=1, keepdims=True) + 1e-12)
            scores = qn @ vn.T
        else:
            dots = queries @ vecs.T
            vf = vecs.astype(jnp.float32)
            v_sq = jnp.sum(vf * vf, axis=1)
            scores = 2.0 * dots - v_sq[None, :]
        return jnp.where(valid_row[None, :], scores, -jnp.inf)

    def _get_search_fn(self, k: int):
        caps = tuple(e.cap_per_shard for e in self._extents)
        bases = tuple(e.base for e in self._extents)
        cache_key = (k, caps, self.dtype)
        fn = self._search_fn_cache.get(cache_key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        metric = self.metric
        int8 = self.dtype == "int8"
        per_ext = 4 if int8 else 2
        score = self._local_scores

        def local_search(queries, *ops):
            # ops per extent: vectors (1,C,D), valid (1,C)[, scales, vsq]
            shard_id = jax.lax.axis_index(DATA_AXIS)
            cand_s, cand_i = [], []
            for e, (C, base) in enumerate(zip(caps, bases)):
                o = ops[e * per_ext:(e + 1) * per_ext]
                ex = (o[2][0], o[3][0]) if int8 else ()
                scores = score(queries, o[0][0], o[1][0], ex, metric, int8)
                s, i = jax.lax.top_k(scores, min(k, C))
                cand_s.append(s)
                # slot ids: extent base + this shard's block + row
                cand_i.append(base + shard_id * C + i)
            s = jnp.concatenate(cand_s, axis=1)
            gi = jnp.concatenate(cand_i, axis=1)
            # local merge BEFORE the gather: cross-chip traffic stays
            # n_shards x B x k however many extents exist
            s, pos = jax.lax.top_k(s, min(k, s.shape[1]))
            gi = jnp.take_along_axis(gi, pos, axis=1)
            all_s = jax.lax.all_gather(s, DATA_AXIS)
            all_i = jax.lax.all_gather(gi, DATA_AXIS)
            B = queries.shape[0]
            cs = jnp.transpose(all_s, (1, 0, 2)).reshape(B, -1)
            ci = jnp.transpose(all_i, (1, 0, 2)).reshape(B, -1)
            ms, mpos = jax.lax.top_k(cs, min(k, cs.shape[1]))
            return ms, jnp.take_along_axis(ci, mpos, axis=1)

        ext_specs = tuple(P(*axes) for axes, _rank
                          in search_operand_layout(self.dtype)[1:])
        in_specs = (P(),) + ext_specs * len(caps)
        shard_fn = _shard_map(
            local_search, mesh=self._mesh,
            in_specs=in_specs,
            out_specs=(P(), P()),
            check_vma=False,
        )
        fn = jax.jit(shard_fn)
        self._search_fn_cache[cache_key] = fn
        return fn

    def _device_topk(self, qmat, fetch_k: int):
        """(scores, global slots) host arrays, best first. Lock held,
        device state flushed."""
        search_fn = self._get_search_fn(fetch_k)
        ops = []
        for ext in self._extents:
            ops += [ext.vectors, ext.valid]
            if self.dtype == "int8":
                ops += [ext.scales, ext.vsq]
        ts, ti = search_fn(qmat, *ops)
        return np.asarray(ts), np.asarray(ti)

    def search(self, queries: list[tuple]) -> list[tuple]:
        """Same contract as ops.knn.BruteForceKnnIndex.search."""
        if not queries:
            return []
        with self._lock:
            if not self._key_to_slot:
                return [() for _ in queries]
            self._flush_to_device()

            max_k = max(int(q[2] or 3) for q in queries)
            has_filter = any(q[3] is not None for q in queries)
            fetch_k = max(1, min(self.cap_per_shard,
                                 max_k * 4 if has_filter else max_k))
            qmat = np.stack([np.asarray(q[1], dtype=np.float32).reshape(-1)
                             for q in queries])
            top_scores, top_idx = self._device_topk(qmat, fetch_k)

            out = []
            for qi, (qkey, qvec, limit, filt) in enumerate(queries):
                limit = int(limit or 3)
                matches = []
                qnorm_sq = None
                for rank in range(top_scores.shape[1]):
                    score = top_scores[qi, rank]
                    if not math.isfinite(score):
                        break
                    key = self._slot_to_key.get(int(top_idx[qi, rank]))
                    if key is None:
                        continue
                    if filt is not None and not self._passes_filter(key, filt):
                        continue
                    if self.metric == KnnMetric.COS:
                        dist = 1.0 - float(score)
                    else:
                        if qnorm_sq is None:
                            q = np.asarray(qvec, dtype=np.float32).reshape(-1)
                            qnorm_sq = float(q @ q)
                        dist = max(0.0, qnorm_sq - float(score))
                    matches.append((key, dist))
                    if len(matches) >= limit:
                        break
                out.append(tuple(matches))
            return out

    def _passes_filter(self, key: Pointer, filt: Any) -> bool:
        return _passes(self._filter_data, key, filt)


# one class, two names (see ops/knn.py PagedKnnIndex)
PagedShardedKnnIndex = ShardedKnnIndex
