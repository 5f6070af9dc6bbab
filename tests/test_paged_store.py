"""Paged HBM vector store (engine/paged_store.py + ops/knn.py
BruteForceKnnIndex + parallel/sharded_knn.py ShardedKnnIndex) and ragged
encoder batching.

The load-bearing contract: THE EXTENT LAYOUT DOES NOT CHANGE AN ANSWER. An
index grown extent by extent answers BYTE-IDENTICALLY to one reserved in a
single extent (the layout every benchmark cell runs: one kernel call, no
merge) across ingest/delete/grow/search churn — same keys, same distances,
bit for bit — and both agree with a numpy float32 exact search that
shares no code with the index. Growth allocates pages instead of
re-uploading, the fused donated ingest grows, and freed pages are reused
(occupancy bounded).
"""

from __future__ import annotations

import numpy as np
import pytest

from pathway_tpu.engine.paged_store import (DevicePagePool, PageAllocator,
                                            PageQuotaExceeded,
                                            live_paged_stats, page_rows)
from pathway_tpu.internals.keys import Pointer
from pathway_tpu.ops.knn import BruteForceKnnIndex, KnnMetric


def _mk(n=None, **kw):
    kw.setdefault("metric", KnnMetric.L2SQ)
    return BruteForceKnnIndex(8, **kw)


def _np_exact(metric, vecs, live, q, k):
    """Exact float32 top-k in plain numpy over the live rows — the
    reference that shares nothing with the index (no kernel, no mirror,
    no ranking code). Returns [(row id, distance)], best first."""
    live = np.asarray(sorted(live))
    v = vecs[live].astype(np.float32)
    q = np.asarray(q, dtype=np.float32)
    if metric == KnnMetric.COS:
        d = 1.0 - (v @ q) / (np.linalg.norm(v, axis=1) * np.linalg.norm(q))
    else:
        d = np.sum((v - q[None, :]) ** 2, axis=1)
    order = np.argsort(d, kind="stable")[:k]
    return [(int(live[i]), float(d[i])) for i in order]


def _extents(idx) -> int:
    return idx.page_stats()["extents"]


def _rows_by_extent(idx) -> dict:
    """Live row ids grouped by the extent their slot lives in."""
    exts = idx._pool.extents if hasattr(idx, "_pool") else idx._extents
    bases = [e.base for e in exts]
    out: dict[int, list[int]] = {}
    for key, slot in idx._key_to_slot.items():
        e = int(np.searchsorted(bases, slot, side="right")) - 1
        out.setdefault(e, []).append(int(key))
    return out


def _probe_rows(idx, per: int = 3) -> list:
    """A few live rows of EVERY extent: queried with their own vectors,
    each extent must put at least its self-matches into the answers, so
    an extent dropped from the merge cannot go unnoticed."""
    return [r for _e, rows in sorted(_rows_by_extent(idx).items())
            for r in sorted(rows)[:per]]


def _answers_span_every_extent(idx, results) -> bool:
    by = _rows_by_extent(idx)
    where = {r: e for e, rows in by.items() for r in rows}
    return {where[int(k)] for res in results for k, _ in res} == set(by)


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

def test_page_rows_validation(monkeypatch):
    assert page_rows(1024) == 1024
    for bad in (100, 96, 1 << 20, 0):
        with pytest.raises(ValueError):
            page_rows(bad)
    monkeypatch.setenv("PATHWAY_PAGE_ROWS", "4096")
    assert page_rows() == 4096
    monkeypatch.setenv("PATHWAY_PAGE_ROWS", "100")
    with pytest.raises(ValueError):
        page_rows()


def test_allocator_alloc_free_reuse():
    a = PageAllocator(128)
    a.add_region(0, 0, 4)  # 4 pages, 512 slots
    slots = [a.take_slot() for _ in range(300)]
    assert len(set(slots)) == 300
    assert a.live_rows == 300
    st = a.stats()
    assert st["pages_total"] == 4 and st["pages_free"] == 1
    # free an entire page's worth: the drained page returns to the pool
    for s in slots:
        a.release_slot(s)
    st = a.stats()
    assert st["pages_free"] == 4 and st["live_rows"] == 0
    # reuse: no growth needed for a fresh fill
    again = [a.take_slot() for _ in range(512)]
    assert len(set(again)) == 512
    with pytest.raises(RuntimeError):
        a.take_slot()  # exhausted without ensure_free/grow


def test_allocator_partial_free_reopens_page():
    a = PageAllocator(128)
    a.add_region(0, 0, 1)
    slots = [a.take_slot() for _ in range(128)]  # page full
    a.release_slot(slots[7])
    assert a.take_slot() == slots[7]  # the freed slot is allocatable again


def test_allocator_tenant_quotas_and_regions():
    a = PageAllocator(128, tenant_quotas={"acme": 2})
    a.add_region(0, 0, 2)
    a.add_region(1, 256, 2)
    acme = [a.take_slot("acme") for _ in range(256)]  # exactly 2 pages
    assert a.tenant_pages["acme"] == 2
    with pytest.raises(PageQuotaExceeded):
        a.take_slot("acme")
    assert a.quota_capped_slots("acme") == 0
    # another tenant still allocates; regions restrict placement
    s = a.take_slot("globex", regions=[1])
    assert 256 <= s < 512
    # freeing acme's pages returns quota headroom
    for s in acme:
        a.release_slot(s)
    assert a.quota_remaining_pages("acme") == 2
    assert a.take_slot("acme") in set(acme) | set(range(512))


def test_pool_grow_appends_extent_without_touching_old():
    pool = DevicePagePool(8, reserved_space=1024, rows_per_page=1024)
    assert pool.capacity == 1024 and len(pool.extents) == 1
    first = pool.extents[0]
    pool.ensure_free(1500)
    assert pool.capacity >= 2048 and pool.extents[0] is first
    assert pool.grow_events >= 1
    # slot→extent mapping and page-aligned bases
    assert pool.extent_index_of(0) == 0
    assert pool.extent_index_of(1024) == 1


# ---------------------------------------------------------------------------
# grown extent by extent vs reserved in one extent: byte-identical across
# churn ("slab" in the names below: the one-extent instance)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", [KnnMetric.L2SQ, KnnMetric.COS])
def test_churn_byte_identical_vs_slab(metric):
    """The acceptance-pinned property: interleaved ingest/delete/grow/
    search — the grown index's top-k == the one-extent index's top-k,
    keys AND distances, byte for byte."""
    rng = np.random.default_rng(11)
    paged = BruteForceKnnIndex(16, metric=metric)
    # 30 steps of at most 400 new rows: never leaves its first extent
    slab = BruteForceKnnIndex(16, metric=metric, reserved_space=30 * 400)
    live: list[int] = []
    next_key = 0

    def step(op):
        nonlocal next_key
        if op == "ingest":
            n = int(rng.integers(50, 400))
            keys = [Pointer(next_key + i) for i in range(n)]
            vecs = rng.normal(size=(n, 16)).astype(np.float32)
            paged.add_batch(keys, vecs)
            slab.add_batch(keys, vecs)
            live.extend(range(next_key, next_key + n))
            next_key += n
        elif op == "delete" and live:
            kill = rng.choice(len(live),
                              size=min(len(live), 120), replace=False)
            for i in sorted(kill, reverse=True):
                k = live.pop(int(i))
                paged.remove(Pointer(k))
                slab.remove(Pointer(k))
        elif op == "update" and live:
            k = int(live[int(rng.integers(len(live)))])
            v = rng.normal(size=(1, 16)).astype(np.float32)
            paged.add_batch([Pointer(k)], v)
            slab.add_batch([Pointer(k)], v)

    ops = rng.choice(["ingest", "delete", "update", "search"], size=30)
    for op in ops:
        step(op)
        if op == "search" or op == ops[-1]:
            qs = [(Pointer(10**9 + i),
                   rng.normal(size=16).astype(np.float32),
                   int(rng.integers(1, 12)), None) for i in range(4)]
            assert paged.search(qs) == slab.search(qs)
    assert _extents(paged) >= 2, "churn never grew the store"
    assert _extents(slab) == 1
    assert len(paged) == len(slab) == len(live)


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_churn_low_precision_paged_matches_slab(dtype):
    rng = np.random.default_rng(3)
    paged = BruteForceKnnIndex(16, metric=KnnMetric.COS, dtype=dtype)
    slab = BruteForceKnnIndex(16, metric=KnnMetric.COS, dtype=dtype,
                              reserved_space=1500)
    keys = [Pointer(i) for i in range(1500)]  # grows past 1024
    vecs = rng.normal(size=(1500, 16)).astype(np.float32)
    paged.add_batch(keys, vecs)
    slab.add_batch(keys, vecs)
    for i in range(0, 600):
        paged.remove(Pointer(i))
        slab.remove(Pointer(i))
    qs = [(Pointer(10**9 + i), vecs[700 + 13 * i], 10, None)
          for i in range(4)]
    rp, rs = paged.search(qs), slab.search(qs)
    assert _extents(paged) >= 2 and _extents(slab) == 1
    for a, b in zip(rp, rs):
        assert [k for k, _ in a] == [k for k, _ in b]
        np.testing.assert_allclose([d for _, d in a], [d for _, d in b],
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the independent reference: numpy float32 exact search over a grown index
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", [KnnMetric.L2SQ, KnnMetric.COS])
def test_grown_index_matches_numpy_oracle(metric):
    rng = np.random.default_rng(21)
    n, dim, k = 2600, 16, 10
    vecs = rng.normal(size=(n, dim)).astype(np.float32)
    idx = BruteForceKnnIndex(dim, metric=metric)
    for lo in range(0, n, 650):  # 1024 + 1024 + 2048 rows: three extents
        idx.add_batch([Pointer(i) for i in range(lo, lo + 650)],
                      vecs[lo:lo + 650])
    removed = set(rng.choice(n, size=900, replace=False).tolist())
    for i in sorted(removed):
        idx.remove(Pointer(i))
    live = set(range(n)) - removed
    assert _extents(idx) >= 3 and len(idx) == len(live)
    qvecs = vecs[_probe_rows(idx)] + np.float32(0.05)
    got = idx.search([(Pointer(10**9 + i), q, k, None)
                      for i, q in enumerate(qvecs)])
    assert _answers_span_every_extent(idx, got)
    for q, res in zip(qvecs, got):
        want = _np_exact(metric, vecs, live, q, k)
        assert [int(key) for key, _ in res] == [i for i, _ in want]
        np.testing.assert_allclose([d for _, d in res],
                                   [d for _, d in want],
                                   rtol=1e-5, atol=1e-5)


def test_filtered_search_and_exhaustive_fallback_paged(monkeypatch):
    import pathway_tpu.ops.knn as knn_mod

    monkeypatch.setattr(knn_mod, "_CHUNK_ROWS", 128)
    idx = _mk()
    rng = np.random.default_rng(4)
    n = 1400  # spans two extents after growth
    vecs = rng.normal(size=(n, 8)).astype(np.float32)
    q = vecs[0] + 100.0
    dists = np.sum((vecs - q) ** 2, axis=1)
    allowed = set(np.argsort(dists)[-3:].tolist())
    idx.add_batch([Pointer(i) for i in range(n)], vecs,
                  filter_data=[{"ok": i in allowed} for i in range(n)])
    res = idx.search([(Pointer(10**9), q, 3,
                       lambda d: bool(d and d["ok"]))])[0]
    assert {int(k) for k, _ in res} == allowed


# ---------------------------------------------------------------------------
# fused donated ingest grows
# ---------------------------------------------------------------------------

def test_fused_ingest_grows_by_allocating_extent():
    import jax.numpy as jnp

    idx = _mk(metric=KnnMetric.COS, dtype="bfloat16")
    ingest = idx.make_fused_ingest(lambda x: x)
    rng = np.random.default_rng(5)
    vals = None
    for base in range(0, 3000, 500):
        vals = jnp.asarray(rng.normal(size=(500, 8)).astype(np.float32))
        ingest([Pointer(base + i) for i in range(500)], vals)
    assert idx.capacity >= 3000
    assert idx.page_stats()["grow_events"] >= 1
    res = idx.search([(Pointer(10**9), np.asarray(vals[17]), 1, None)])
    assert res[0][0][0] == Pointer(2500 + 17)


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_fused_ingest_grown_matches_one_extent(dtype):
    """The donated one-dispatch ingest growing into fresh extents answers
    byte for byte as the same ingest into one reserved extent."""
    import jax.numpy as jnp

    rng = np.random.default_rng(22)
    n = 3000
    vecs = rng.normal(size=(n, 8)).astype(np.float32)
    grown = _mk(metric=KnnMetric.COS, dtype=dtype)
    one = _mk(metric=KnnMetric.COS, dtype=dtype, reserved_space=n)
    for idx in (grown, one):
        ingest = idx.make_fused_ingest(lambda x: x)
        for lo in range(0, n, 500):
            ingest([Pointer(lo + i) for i in range(500)],
                   jnp.asarray(vecs[lo:lo + 500]))
        # every batch took the donated dispatch: nothing went through
        # the two-dispatch scatter
        assert idx.upload_rows_total == 0
    assert _extents(grown) >= 2 and _extents(one) == 1
    assert grown.page_stats()["grow_events"] >= 1
    for i in range(0, n, 3):
        grown.remove(Pointer(i))
        one.remove(Pointer(i))
    qs = [(Pointer(10**9 + i), vecs[r], 12, None)
          for i, r in enumerate(_probe_rows(grown))]
    rg, ro = grown.search(qs), one.search(qs)
    assert _answers_span_every_extent(grown, rg)
    assert rg == ro
    assert all(len(r) == 12 and int(k) % 3 for r in rg for k, _ in r)


def test_fused_ingest_quota_exceeded_is_not_swallowed():
    import jax.numpy as jnp

    idx = _mk(tenant="acme", tenant_quotas={"acme": 1024})
    ingest = idx.make_fused_ingest(lambda x: x)
    ingest([Pointer(i) for i in range(1024)], jnp.zeros((1024, 8)))
    with pytest.raises(PageQuotaExceeded):
        ingest([Pointer(5000)], jnp.zeros((1, 8)))


# ---------------------------------------------------------------------------
# page reuse: occupancy bounded under churn
# ---------------------------------------------------------------------------

def test_freed_pages_are_reused_occupancy_bounded():
    idx = _mk()
    rng = np.random.default_rng(6)
    key = 0
    for _round in range(8):
        keys = [Pointer(key + i) for i in range(1000)]
        idx.add_batch(keys, rng.normal(size=(1000, 8)).astype(np.float32))
        idx.search([(Pointer(10**9), np.zeros(8, np.float32), 3, None)])
        for k in keys:
            idx.remove(k)
        key += 1000
    st = idx.page_stats()
    # 8000 rows churned through a store that never needs more than ~2
    # extents: freed pages were reused, not leaked
    assert st["pages_total"] <= 4, st
    assert st["grow_events"] <= 2, st
    assert st["live_rows"] == 0


def test_tenant_quota_enforced_on_add_batch():
    idx = _mk(tenant="acme", tenant_quotas={"acme": 2048})
    rng = np.random.default_rng(7)
    idx.add_batch([Pointer(i) for i in range(2048)],
                  rng.normal(size=(2048, 8)).astype(np.float32))
    with pytest.raises(PageQuotaExceeded):
        idx.add_batch([Pointer(9000)],
                      rng.normal(size=(1, 8)).astype(np.float32))
    # freeing rows frees pages back under quota
    for i in range(2048):
        idx.remove(Pointer(i))
    idx.add_batch([Pointer(9000)],
                  rng.normal(size=(1, 8)).astype(np.float32))
    assert len(idx) == 1


# ---------------------------------------------------------------------------
# stats surfaces
# ---------------------------------------------------------------------------

def test_live_paged_stats_aggregates():
    idx = _mk(tenant="acme")
    idx.add_batch([Pointer(i) for i in range(10)],
                  np.zeros((10, 8), np.float32))
    st = live_paged_stats()
    assert st is not None
    assert st["pages_total"] >= 1
    assert st["page_rows"] == idx.page_stats()["page_rows"]
    assert "acme" in st["tenants"]


def test_add_batch_device_and_mirror_sync_across_extents():
    import jax.numpy as jnp

    rng = np.random.default_rng(8)
    vecs = rng.normal(size=(1500, 8)).astype(np.float32)
    host = _mk()
    dev = _mk()
    keys = [Pointer(i) for i in range(1500)]
    host.add_batch(keys, vecs)
    dev.add_batch_device(keys, jnp.asarray(vecs))
    q = [(Pointer(900 + i), vecs[i * 7], 5, None) for i in range(4)]
    assert host.search(q) == dev.search(q)
    # exact host-side read path syncs the stale mirror per extent
    got = dev._exhaustive_filtered_search(vecs[1400], 1, lambda d: True)
    assert got[0][0] == Pointer(1400)


def test_latency_probe_multi_extent():
    idx = _mk()
    rng = np.random.default_rng(9)
    idx.add_batch([Pointer(i) for i in range(1500)],
                  rng.normal(size=(1500, 8)).astype(np.float32))
    idx.search([(Pointer(10**9), np.zeros(8, np.float32), 3, None)])
    assert len(idx._pool.extents) >= 2
    ms = idx.latency_probe(batch_size=1, k=5, reps=4)
    assert ms > 0.0


# ---------------------------------------------------------------------------
# sharded paged store
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh4():
    import os

    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    from pathway_tpu.parallel.mesh import MeshConfig, make_mesh

    return make_mesh(MeshConfig(data=4, model=1))


def test_sharded_paged_grow_without_remap(mesh4):
    from pathway_tpu.parallel.sharded_knn import ShardedKnnIndex

    idx = ShardedKnnIndex(8, mesh=mesh4, reserved_space=8, page_rows=128)
    assert idx.cap_per_shard == 128  # page-aligned minimum
    rng = np.random.default_rng(10)
    n = idx.total_capacity + 200
    vecs = rng.normal(size=(n, 8)).astype(np.float32)
    keys = [Pointer(i) for i in range(n)]
    idx.add_batch(keys, vecs)
    slot_snapshot = dict(idx._key_to_slot)
    idx.add_batch([Pointer(n)],
                  rng.normal(size=(1, 8)).astype(np.float32))
    # online growth: NO slot was remapped
    assert all(idx._key_to_slot[k] == s for k, s in slot_snapshot.items())
    for probe in (0, n // 2, n - 1):
        res = idx.search([(Pointer(10**6), vecs[probe], 1, None)])
        assert res[0] and res[0][0][0] == Pointer(probe)
    assert idx.page_stats()["grow_events"] >= 1


def test_sharded_paged_tenant_quota_enforced(mesh4):
    from pathway_tpu.parallel.sharded_knn import ShardedKnnIndex

    idx = ShardedKnnIndex(8, mesh=mesh4, reserved_space=8, page_rows=128,
                          tenant="acme",
                          tenant_quotas={"acme": 512})  # 4 pages
    rng = np.random.default_rng(13)
    idx.add_batch([Pointer(i) for i in range(512)],
                  rng.normal(size=(512, 8)).astype(np.float32))
    with pytest.raises(PageQuotaExceeded):
        idx.add_batch([Pointer(9000)],
                      rng.normal(size=(1, 8)).astype(np.float32))
    for i in range(512):
        idx.remove(Pointer(i))
    idx.add_batch([Pointer(9000)],
                  rng.normal(size=(1, 8)).astype(np.float32))
    assert len(idx) == 1


def test_sharded_paged_matches_contiguous(mesh4):
    from pathway_tpu.parallel.sharded_knn import ShardedKnnIndex

    rng = np.random.default_rng(12)
    vecs = rng.normal(size=(700, 8)).astype(np.float32)
    keys = [Pointer(i) for i in range(700)]
    paged = ShardedKnnIndex(8, mesh=mesh4, reserved_space=8, page_rows=128)
    flat = ShardedKnnIndex(8, mesh=mesh4, reserved_space=700)
    paged.add_batch(keys, vecs)
    flat.add_batch(keys, vecs)
    assert _extents(paged) >= 2 and _extents(flat) == 1
    for i in range(0, 700, 2):
        paged.remove(Pointer(i))
        flat.remove(Pointer(i))
    qs = [(Pointer(10**6 + i), vecs[101 + 2 * i], 6, None)
          for i in range(3)]
    rp, rf = paged.search(qs), flat.search(qs)
    for a, b in zip(rp, rf):
        assert [k for k, _ in a] == [k for k, _ in b]
        np.testing.assert_allclose([d for _, d in a], [d for _, d in b],
                                   rtol=1e-5, atol=1e-5)


def _sharded_churn(mesh4, n, rng, **kw):
    """A sharded index grown across extents by add/remove/add, and its
    twin reserved in one extent; returns (grown, one, vecs, live)."""
    from pathway_tpu.parallel.sharded_knn import ShardedKnnIndex

    vecs = rng.normal(size=(n, 8)).astype(np.float32)
    grown = ShardedKnnIndex(8, mesh=mesh4, reserved_space=8, page_rows=128,
                            **kw)
    one = ShardedKnnIndex(8, mesh=mesh4, reserved_space=n, page_rows=128,
                          **kw)
    half = n // 2
    for idx in (grown, one):
        idx.add_batch([Pointer(i) for i in range(half)], vecs[:half])
        idx.search([(Pointer(10**6), vecs[0], 1, None)])  # flush, then churn
        for i in range(0, half, 3):
            idx.remove(Pointer(i))
        idx.add_batch([Pointer(i) for i in range(half, n)], vecs[half:])
    live = {i for i in range(half) if i % 3} | set(range(half, n))
    assert _extents(grown) >= 3 and _extents(one) == 1
    assert len(grown) == len(one) == len(live)
    return grown, one, vecs, live


@pytest.mark.parametrize("metric", [KnnMetric.L2SQ, KnnMetric.COS])
def test_sharded_int8_grown_matches_one_extent(mesh4, metric):
    rng = np.random.default_rng(23)
    grown, one, vecs, live = _sharded_churn(mesh4, 1300, rng, metric=metric,
                                            dtype="int8")
    qs = [(Pointer(10**6 + i), vecs[r], 8, None)
          for i, r in enumerate(_probe_rows(grown))]
    rg, ro = grown.search(qs), one.search(qs)
    assert _answers_span_every_extent(grown, rg)
    assert rg == ro
    assert all(int(k) in live for r in rg for k, _ in r)


@pytest.mark.parametrize("metric", [KnnMetric.L2SQ, KnnMetric.COS])
def test_sharded_grown_matches_numpy_oracle(mesh4, metric):
    rng = np.random.default_rng(24)
    grown, _one, vecs, live = _sharded_churn(mesh4, 1300, rng,
                                             metric=metric)
    qvecs = vecs[_probe_rows(grown)] + np.float32(0.05)
    got = grown.search([(Pointer(10**6 + i), q, 8, None)
                        for i, q in enumerate(qvecs)])
    assert _answers_span_every_extent(grown, got)
    for q, res in zip(qvecs, got):
        want = _np_exact(metric, vecs, live, q, 8)
        assert [int(key) for key, _ in res] == [i for i, _ in want]
        np.testing.assert_allclose([d for _, d in res],
                                   [d for _, d in want],
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# ragged encoder batching
# ---------------------------------------------------------------------------

def _tiny_embedders(**kw):
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    cfg = EncoderConfig.tiny(compute_dtype=jnp.float32, **kw)
    return (JaxEncoderEmbedder(config=cfg, ragged=True, max_len=64),
            JaxEncoderEmbedder(config=cfg, ragged=False, max_len=64))


TEXTS = ["hello world foo", "a",
         "some much longer text with many more words than the others "
         "to span packing widths", "mid size text here ok"] * 9


@pytest.mark.parametrize("pooling", ["cls", "mean"])
def test_ragged_encode_matches_per_row(pooling):
    ragged, plain = _tiny_embedders(pooling=pooling)
    er = np.asarray(ragged.encode_batch_device(TEXTS))
    ep = np.asarray(plain.encode_batch_device(TEXTS))
    assert er.shape == ep.shape
    cos = np.sum(er * ep, axis=1)
    assert cos.min() > 0.9999, cos.min()


def test_ragged_packing_shapes_and_order():
    ragged, _ = _tiny_embedders()
    chunks = ragged.pack_ragged(TEXTS)
    n_docs = sum(c[1] for c in chunks)
    assert n_docs == len(TEXTS)
    for (ids, doc_map, pos, dseq, doff), n_real, n_pad in chunks:
        assert ids.shape == doc_map.shape == pos.shape
        assert ids.shape[0] in ragged.ragged_buckets()
        assert dseq.shape == doff.shape == (n_pad,)
        # docs numbered 0..n_real-1 in input order; padding rows -1
        assert set(np.unique(doc_map)) <= set(range(-1, n_real))


def test_ragged_fused_ingest_end_to_end():
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.ops.knn import DeviceEmbeddingKnnIndex
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    cfg = EncoderConfig.tiny()
    emb = JaxEncoderEmbedder(config=cfg, ragged=True, max_len=64)
    inner = BruteForceKnnIndex(cfg.hidden, metric=KnnMetric.COS,
                               dtype="bfloat16")
    idx = DeviceEmbeddingKnnIndex(emb, inner)
    texts = [f"document number {i} with content {i * 7}" for i in range(150)]
    idx.add_batch([Pointer(i) for i in range(150)], texts)
    assert len(idx) == 150
    res = idx.search([(Pointer(10**9), texts[42], 1, None)])
    assert res[0][0][0] == Pointer(42)


def test_ragged_warmup_compile_count_under_six():
    import pathway_tpu as pw
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.ops.knn import DeviceEmbeddingKnnIndex
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    cfg = EncoderConfig.tiny(max_len=512)
    emb = JaxEncoderEmbedder(config=cfg, ragged=True, max_len=512)
    idx = DeviceEmbeddingKnnIndex(
        emb, BruteForceKnnIndex(cfg.hidden, metric=KnnMetric.COS))
    out = pw.warmup(emb, index=idx)
    # leaked gc-pending fused programs from other tests may add autojit
    # entries — the ragged ladder is what this pin counts
    ladder = [e for e in out["compiled"] if e[0] != "autojit"]
    assert 0 < len(ladder) <= 6, out["compiled"]
    assert len(idx) == 0  # warmup scratch rows retracted
    # the width-bucket zoo this replaces is ~18 compiles
    assert len(emb.bucket_widths()) >= 15


# ---------------------------------------------------------------------------
# the stages a search and an ingest call record of themselves while a flight
# recorder is on (engine/flight_recorder.py ``live_span``)
# ---------------------------------------------------------------------------

@pytest.fixture
def live_recorder():
    """A recorder that is on, as ``pw.run`` leaves one while it traces."""
    from pathway_tpu.engine.flight_recorder import FlightRecorder

    rec = FlightRecorder.from_env(auto_on=True)
    try:
        yield rec
    finally:
        rec.enabled = False


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] <= child[2] <= parent[2]


def _text_index():
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.ops.knn import DeviceEmbeddingKnnIndex
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    cfg = EncoderConfig.tiny()
    emb = JaxEncoderEmbedder(config=cfg, ragged=True, max_len=64)
    return DeviceEmbeddingKnnIndex(
        emb, BruteForceKnnIndex(cfg.hidden, metric=KnnMetric.COS))


def test_a_vector_index_searched_directly_records_one_search(live_recorder):
    idx = _mk(reserved_space=64)
    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((6, 8)).astype(np.float32)
    idx.add_batch([Pointer(i) for i in range(6)], vecs)
    assert idx.search([]) == [] and not live_recorder.spans()
    (hits,) = idx.search([(Pointer(99), vecs[2], 2, None)])
    assert hits[0][0] == Pointer(2)
    scan, search = live_recorder.spans()
    assert (scan[0], search[0]) == ("search.scan", "index.search")
    assert _inside(scan, search) and scan[3] is None is search[3]
    assert scan[5]["queries"] == 1 and scan[5]["fetch_k"] == 2
    assert scan[5]["extents"] == 1
    assert 0 <= scan[5]["dispatch_ms"] <= (scan[2] - scan[1]) * 1e3
    # the six pending rows went to the device inside this search
    counts = search[5]
    assert counts["queries"] == 1 and counts["flush_rows"] == 6
    assert counts["rounds"] == 1
    assert counts["prepare_ms"] + counts["rank_ms"] \
        <= (search[2] - search[1]) * 1e3
    # the steady state flushes nothing; two queries are one search
    idx.search([(Pointer(98), vecs[1], 1, None),
                (Pointer(97), vecs[3], 1, None)])
    search = live_recorder.spans()[-1]
    assert search[5]["queries"] == 2 and search[5]["flush_rows"] == 0


def test_a_selective_filter_s_rounds_are_counted_on_the_search(
        live_recorder):
    n = 40
    idx = _mk(reserved_space=64)
    rng = np.random.default_rng(1)
    vecs = rng.standard_normal((n, 8)).astype(np.float32)
    order = np.argsort(((vecs - vecs[0]) ** 2).sum(axis=1))
    # only the farthest row passes: the first candidate lists hold none
    idx.add_batch([Pointer(i) for i in range(n)], vecs,
                  filter_data=[{"ok": i == order[-1]} for i in range(n)])
    (hits,) = idx.search([(Pointer(99), vecs[0], 1, lambda d: d["ok"])])
    assert hits[0][0] == Pointer(int(order[-1]))
    spans = live_recorder.spans()
    scans = [sp for sp in spans if sp[0] == "search.scan"]
    (search,) = [sp for sp in spans if sp[0] == "index.search"]
    assert search[5]["rounds"] == len(scans) > 1
    assert [sp[5]["fetch_k"] for sp in scans] == sorted(
        sp[5]["fetch_k"] for sp in scans)
    assert all(_inside(sp, search) for sp in scans)


def test_a_text_index_records_its_search_once_and_its_stages_inside(
        live_recorder):
    idx = _text_index()
    texts = [f"document number {i} with content {i * 7}" for i in range(9)]
    idx.add_batch([Pointer(i) for i in range(9)], texts)
    by_name = {sp[0]: sp for sp in live_recorder.spans()}
    assert set(by_name) == {"embedder.tokenize", "embedder.pack",
                            "embedder.dispatch", "index.add_batch"}
    add, pack = by_name["index.add_batch"], by_name["embedder.pack"]
    assert add[5] == {"docs": 9, "dispatches": 1, "fused": 1}
    assert _inside(by_name["embedder.tokenize"], pack)
    assert _inside(pack, add) and _inside(by_name["embedder.dispatch"], add)
    assert pack[2] <= by_name["embedder.dispatch"][1]
    tokens = by_name["embedder.tokenize"][5]["tokens"]
    assert pack[5]["texts"] == 9 and pack[5]["tokens"] == tokens
    assert pack[5]["slots"] == pack[5]["rows"] * 64 >= tokens
    assert by_name["embedder.dispatch"][5]["tokens"] == tokens
    before = len(live_recorder.spans())
    (hits,) = idx.search([(Pointer(99), texts[4], 1, None)])
    assert hits[0][0] == Pointer(4)
    spans = live_recorder.spans()[before:]
    # one ``index.search``: the wrapped index hands its counts up and
    # writes no span of its own
    assert [sp[0] for sp in spans] == [
        "embedder.tokenize", "embedder.pack", "search.embed",
        "search.scan", "index.search"]
    tokenize, pack, embed, scan, search = spans
    assert _inside(tokenize, pack) and _inside(pack, embed)
    assert _inside(embed, search) and _inside(scan, search)
    assert embed[2] <= scan[1]
    assert search[5]["queries"] == embed[5]["queries"] == 1
    assert {"flush_rows", "prepare_ms", "rank_ms", "rounds"} <= set(
        search[5])


def test_the_padded_packer_records_the_same_two_spans(live_recorder):
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    emb = JaxEncoderEmbedder(config=EncoderConfig.tiny(), max_len=64)
    ids, lens = emb.pack_tokens(["one passage", "another longer passage"])
    tokenize, pack = live_recorder.spans()
    assert (tokenize[0], pack[0]) == ("embedder.tokenize", "embedder.pack")
    assert _inside(tokenize, pack)
    assert pack[5] == {"texts": 2, "rows": ids.shape[0],
                       "slots": ids.size, "tokens": int(lens.sum())}


def test_the_two_dispatch_path_says_so_on_the_ingest_span(live_recorder):
    from pathway_tpu.ops.knn import FusedIngestUnplaceable

    idx = _text_index()

    def unplaceable(*_args, **_kw):
        raise FusedIngestUnplaceable("the batch fits no single extent")

    idx._fused = unplaceable
    idx.add_batch([Pointer(1), Pointer(2)], ["one passage", "another"])
    assert len(idx) == 2 and idx.fused_fallbacks == 1
    (add,) = [sp for sp in live_recorder.spans()
              if sp[0] == "index.add_batch"]
    assert add[5] == {"docs": 2, "dispatches": 1, "fused": 0}
    # the fallback packs the batch again: both packs lie inside the call
    packs = [sp for sp in live_recorder.spans() if sp[0] == "embedder.pack"]
    assert len(packs) == 2 and all(_inside(sp, add) for sp in packs)


@pytest.mark.parametrize("recording, clock, spans", [(False, 0, 0),
                                                     (True, None, 11)])
def test_a_search_and_an_ingest_call_read_no_clock_with_no_recorder_on(
        recording, clock, spans):
    """Off is free (the guard is tests/trace_canary.py's): no clock read,
    no tuple. On: four spans an ingest call, five a text search, two a
    vector search."""
    from tests.trace_canary import index_clock_reads

    reads, written = index_clock_reads(recording)
    assert written == spans
    assert reads == clock if clock is not None else reads > 0


# ---------------------------------------------------------------------------
# a text search keeps its data on the device between the packer and the
# ranking loop: one upload (the packer's five arrays as one buffer), the
# embedding handed from the encoder to the scan, one fetch an extent
# ---------------------------------------------------------------------------

_DOCS = [f"document number {i} with content {i * 7}" for i in range(1100)]
_QUERIES = [_DOCS[4], "content 91 of a document", _DOCS[1050],
            "number with", _DOCS[131]]
_RARE = (7, 600, 1090)


@pytest.fixture(scope="module")
def text_embedder():
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    return JaxEncoderEmbedder(config=EncoderConfig.tiny(), ragged=True,
                              max_len=64)


@pytest.fixture(scope="module")
def text_indexes(text_embedder):
    """One text index a (metric, slab dtype, extents), built when first
    asked for: the 1,100 documents in one reserved extent or grown into
    a second, every third row allowed by the filter ``ok`` and three rows
    by ``rare``."""
    from pathway_tpu.ops.knn import DeviceEmbeddingKnnIndex

    built = {}

    def get(metric, dtype, extents):
        key = (metric, dtype, extents)
        if key not in built:
            inner = BruteForceKnnIndex(
                text_embedder.config.hidden, metric=metric, dtype=dtype,
                reserved_space=2048 if extents == 1 else 0)
            idx = DeviceEmbeddingKnnIndex(text_embedder, inner)
            for lo in range(0, len(_DOCS), 100):
                idx.add_batch(
                    [Pointer(i) for i in range(lo, lo + 100)],
                    _DOCS[lo:lo + 100],
                    [{"ok": i % 3 == 0, "rare": i in _RARE}
                     for i in range(lo, lo + 100)])
            assert idx.fused_fallbacks == 0
            assert _extents(inner) == extents
            built[key] = idx
        return built[key]

    return get


@pytest.mark.parametrize("filt", [None, "ok", "rare", "rare-exhaustive"])
@pytest.mark.parametrize("batch", [1, 5])
@pytest.mark.parametrize("extents", [1, 2])
@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("metric", [KnnMetric.COS, KnnMetric.L2SQ])
def test_a_text_search_answers_as_the_inner_index_given_the_fetched_rows(
        text_embedder, text_indexes, monkeypatch, metric, dtype, extents,
        batch, filt):
    """Same keys, same distances to the last bit as the path that fetched
    the embeddings and uploaded them again: the same float32 rows reach
    the same scan. ``ok`` escalates the fetch once or twice, ``rare``
    until every row is a candidate, and with the chunk cut to 32 rows a
    scan cannot list them all: the exact pass over the host mirror."""
    import pathway_tpu.ops.knn as knn_mod

    idx = text_indexes(metric, dtype, extents)
    if filt == "rare-exhaustive":
        monkeypatch.setattr(knn_mod, "_CHUNK_ROWS", 32)
    name = filt and filt.split("-")[0]
    test = name and (lambda d: bool(d and d[name]))
    texts = _QUERIES[:batch]
    fetched = np.asarray(text_embedder.encode_batch_device(texts),
                         dtype=np.float32)
    by_text = idx.search([(Pointer(10**9 + i), t, 3, test)
                          for i, t in enumerate(texts)])
    by_rows = idx.inner.search([(Pointer(10**9 + i), fetched[i], 3, test)
                                for i in range(batch)])
    assert by_text == by_rows
    assert all(len(r) == 3 for r in by_text)
    if filt is None:
        assert by_text[0][0][0] == Pointer(4)
    elif name == "rare":
        assert all({int(k) for k, _ in r} == set(_RARE) for r in by_text)
    else:
        assert all(int(k) % 3 == 0 for r in by_text for k, _ in r)


def _search_counts(rec, idx, queries) -> dict:
    before = len(rec.spans())
    idx.search(queries)
    (search,) = [sp for sp in rec.spans()[before:]
                 if sp[0] == "index.search"]
    return search[5]


def test_a_search_counts_its_uploads_and_fetches_on_its_span(
        live_recorder, text_indexes):
    """One upload and one fetch a text query, and as many a vector query
    (its matrix, its result); a fetch more an extent scanned, one more
    where an L2 distance asks for the query's vector on the host."""
    import jax

    one = text_indexes(KnnMetric.COS, "bfloat16", 1)
    text = [(Pointer(10**9), _QUERIES[0], 3, None)]
    counts = _search_counts(live_recorder, one, text)
    assert (counts["uploads"], counts["fetches"]) == (1, 1)
    five = [(Pointer(10**9 + i), t, 3, None)
            for i, t in enumerate(_QUERIES)]
    counts = _search_counts(live_recorder, one, five)
    assert (counts["uploads"], counts["fetches"]) == (1, 1)
    row = np.asarray(one.embedder.encode_batch_device([_QUERIES[0]]))[0]
    counts = _search_counts(live_recorder, one.inner,
                            [(Pointer(10**9), row, 3, None)])
    assert (counts["uploads"], counts["fetches"]) == (1, 1)
    counts = _search_counts(
        live_recorder, text_indexes(KnnMetric.COS, "bfloat16", 2), text)
    assert (counts["uploads"], counts["fetches"]) == (1, 2)
    counts = _search_counts(
        live_recorder, text_indexes(KnnMetric.L2SQ, "bfloat16", 1), text)
    assert (counts["uploads"], counts["fetches"]) == (1, 2)
    # explicit transfers only: nothing rides a dispatch or an eager
    # operation as a host operand
    with jax.transfer_guard("disallow"):
        assert one.search(five)[0][0][0] == Pointer(4)
        assert one.inner.search([(Pointer(10**9), row, 3, None)])


def test_the_query_path_s_programs_keep_their_module_names(text_embedder,
                                                           text_indexes):
    """The benchmark finds the query path's encoder and scan in a profile
    by the names JAX gives their modules (benchmark/lib/trace.py
    ``MODULE_PATTERNS``)."""
    import jax
    import jax.numpy as jnp

    import pathway_tpu.ops.knn as knn_mod

    # one sequence of 64 tokens: three arrays of 64, two of its 4 documents
    flat = jax.ShapeDtypeStruct((3 * 64 + 2 * 4,), jnp.int32)
    lowered = text_embedder._encode_ragged.lower(text_embedder.params, flat)
    assert "module @jit_ragged_device_producer " in lowered.as_text()
    for dtype in ("bfloat16", "int8"):
        inner = text_indexes(KnnMetric.COS, dtype, 1).inner
        (ext,) = inner._pool.extents
        lowered = knn_mod._packed_search_fn(inner._get_search_fn(3)).lower(
            jnp.zeros((1, inner.dim), jnp.float32), ext.vectors,
            inner._extent_extras(ext), ext.valid)
        assert "module @jit_search " in lowered.as_text()


def test_a_warmed_process_compiles_nothing_at_a_text_query(monkeypatch):
    """``pw.warmup`` walks the program the query path calls (the chunk as
    one buffer) at every sequence bucket: with the scan and the slice of
    each batch compiled before (they are shared by every index and
    embedder; the harness's ``warm_queries`` walks them), a fresh
    embedder's first text search of each bucket compiles nothing."""
    import pathway_tpu as pw
    from pathway_tpu.engine import device_sanitizer as ds
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.ops.knn import DeviceEmbeddingKnnIndex
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    long = " ".join(["word"] * 40)  # two of them overflow a row of 64

    def index():
        emb = JaxEncoderEmbedder(config=EncoderConfig.tiny(), ragged=True,
                                 max_len=64, ragged_max_seqs=4)
        idx = DeviceEmbeddingKnnIndex(emb, BruteForceKnnIndex(
            emb.config.hidden, metric=KnnMetric.COS, reserved_space=256))
        idx.add_batch([Pointer(i) for i in range(20)], _DOCS[:20])
        return emb, idx

    def search_each_bucket(emb, idx):
        for n_seqs in emb.ragged_buckets():
            texts = [long] * n_seqs
            assert emb.pack_ragged(texts)[0][0][0].shape[0] == n_seqs
            idx.search([(Pointer(10**9 + i), t, 3, None)
                        for i, t in enumerate(texts)])

    search_each_bucket(*index())  # the shared scan and slice programs
    ds._reset_for_tests()
    monkeypatch.setenv("PATHWAY_DEVICE_SANITIZER", "1")
    try:
        emb, idx = index()
        out = pw.warmup(emb, index=idx, ks=(3,))
        assert [shape for kind, shape in out["compiled"]
                if kind == "ragged_encode"] == [(1, 64), (2, 64), (4, 64)]
        assert ds.in_steady_state() and ds.warmup_compiles() > 0
        search_each_bucket(emb, idx)
        assert ds.post_warmup_compiles() == 0
        assert ds.violations() == []
    finally:
        ds._reset_for_tests()


def test_a_search_s_coverage_is_listed_once_an_established_extent():
    """Every search records the pages it scanned (the result cache's
    coverage): the set is made when an extent is established, not at
    every search."""
    idx = _mk(metric=KnnMetric.COS)
    rng = np.random.default_rng(3)
    vecs = rng.normal(size=(1400, 8)).astype(np.float32)
    idx.add_batch([Pointer(i) for i in range(600)], vecs[:600])
    q = [(Pointer(10**9), vecs[5], 3, None)]
    idx.search(q)
    first = idx._pool.touched_page_ids()
    assert first == frozenset(range(idx.capacity // page_rows()))
    idx.search(q)
    assert idx._pool.touched_page_ids() is first
    idx.add_batch([Pointer(i) for i in range(600, 1400)], vecs[600:])
    assert _extents(idx) == 2 and idx._pool.touched_page_ids() is first
    idx.search(q)  # the flush establishes the second extent
    assert idx._pool.touched_page_ids() == frozenset(
        range(idx.capacity // page_rows()))
    if idx.result_cache is not None:
        assert idx.last_search_coverage is idx._pool.touched_page_ids()
