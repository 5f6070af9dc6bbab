"""Paged HBM vector store: device page pool + host-side page table.

The KNN index (ops/knn.py, parallel/sharded_knn.py) keeps its vectors in
the paged-memory design from Ragged Paged Attention (PAPERS.md): HBM is
carved into fixed-size pages (``PATHWAY_PAGE_ROWS`` vector rows each, plus
per-row validity and — for int8 rows — quantization scale/norm side
columns), a host-side page table maps logical slots to (page, offset), and
device memory is allocated in page-aligned **extents** that are never
moved or copied once created:

- growth appends a new extent (fresh device allocation, established as
  zeros ON DEVICE) — existing extents, and the donated buffers the fused
  ingest scatters into, are untouched (EdgeRAG-style online indexing: no
  re-quantization copies);
- frees return pages to a free list, so delete/ingest churn reuses pages
  instead of growing the pool (occupancy stays bounded);
- pages carry a tenant tag with optional per-tenant page quotas — the
  allocation unit for many small indexes sharing one device.

The pool owns page accounting and the per-extent device/host bookkeeping
containers; the search/scatter kernels stay in ops/knn.py and
parallel/sharded_knn.py (they operate per extent). Callers hold the owning
index's lock around every pool call — the pool itself is not synchronized.
"""

from __future__ import annotations

import os
import weakref
from typing import Any, Hashable

import numpy as np

_DEFAULT_PAGE_ROWS = 1024


class PageQuotaExceeded(RuntimeError):
    """A tenant asked for pages beyond its configured quota. Growth cannot
    help (the quota, not the pool, is the limit), so this escapes instead
    of looping the grow path."""


def page_rows(override: int | None = None) -> int:
    """Rows per page. Must be a power of two in [128, 2^19] so pages tile
    both the 128-lane layout and the chunked-scan kernel's chunk size
    (ops/knn.py ``_CHUNK_ROWS``)."""
    rows = override if override is not None else int(
        os.environ.get("PATHWAY_PAGE_ROWS", _DEFAULT_PAGE_ROWS))
    if rows < 128 or rows > (1 << 19) or rows & (rows - 1):
        raise ValueError(
            f"page_rows must be a power of two in [128, {1 << 19}]; got "
            f"{rows} (PATHWAY_PAGE_ROWS)")
    return rows


def quota_pages(quota_rows: int, rows_per_page: int) -> int:
    """Pages a row quota buys — rounded UP, so a non-page-aligned quota
    silently over-grants (the static checker flags this as PWT111)."""
    return -(-int(quota_rows) // rows_per_page)


class _Page:
    __slots__ = ("pid", "base", "region", "free", "live", "tenant")

    def __init__(self, pid: int, base: int, region: Hashable,
                 rows: int):
        self.pid = pid
        self.base = base          # global row id of offset 0
        self.region = region      # (extent index) or (extent, shard)
        self.free = list(range(rows - 1, -1, -1))  # LIFO offsets
        self.live = 0
        self.tenant: Hashable | None = None


class PageAllocator:
    """Host-side page table: slot allocation within fixed-size pages.

    Pages belong to a *region* (the device extent — or (extent, shard)
    block for the mesh-sharded store) fixed at registration, and are
    claimed by a *tenant* on first allocation. A page with live rows is
    "open" for its tenant; a page whose last row is freed returns to its
    region's free list (tenant tag cleared) — the reuse that keeps
    occupancy bounded under ingest/delete churn.

    Global row ids are contiguous across regions and every region base is
    page-aligned, so ``slot // page_rows`` IS the page id — the page table
    needs no search structure.
    """

    def __init__(self, rows_per_page: int,
                 tenant_quotas: dict[Hashable, int] | None = None):
        self.page_rows = int(rows_per_page)
        self.pages: list[_Page] = []
        # region → LIFO of unclaimed page ids; insertion order preserved
        self._free_pages: dict[Hashable, list[int]] = {}
        # (tenant, region) → page ids with free slots, claimed by tenant
        self._open: dict[tuple, list[int]] = {}
        self.tenant_pages: dict[Hashable, int] = {}
        # quotas in PAGES (callers convert rows via quota_pages)
        self.tenant_quota_pages: dict[Hashable, int] | None = (
            dict(tenant_quotas) if tenant_quotas else None)
        self.live_rows = 0

    # -- registration -------------------------------------------------------
    def add_region(self, region: Hashable, base: int, n_pages: int) -> None:
        if base % self.page_rows:
            raise ValueError(
                f"region base {base} not aligned to page_rows "
                f"{self.page_rows}")
        pids = []
        for i in range(n_pages):
            pid = len(self.pages)
            self.pages.append(_Page(
                pid, base + i * self.page_rows, region, self.page_rows))
            pids.append(pid)
        # LIFO free list: reversed so lower page ids are taken first
        self._free_pages.setdefault(region, []).extend(reversed(pids))

    @property
    def n_pages(self) -> int:
        return len(self.pages)

    @property
    def n_free_pages(self) -> int:
        return sum(len(v) for v in self._free_pages.values())

    # -- quota accounting ---------------------------------------------------
    def quota_remaining_pages(self, tenant: Hashable) -> int | None:
        """Pages ``tenant`` may still claim (None = unlimited)."""
        if self.tenant_quota_pages is None:
            return None
        quota = self.tenant_quota_pages.get(tenant)
        if quota is None:
            return None
        return max(0, quota - self.tenant_pages.get(tenant, 0))

    def quota_capped_slots(self, tenant: Hashable) -> int | None:
        """Upper bound on slots ``tenant`` can EVER reach from here
        (open-page slack + quota'd fresh pages), growth included. None =
        unbounded."""
        rem = self.quota_remaining_pages(tenant)
        if rem is None:
            return None
        return self._open_slack(tenant) + rem * self.page_rows

    def _open_slack(self, tenant: Hashable) -> int:
        return sum(
            len(self.pages[pid].free)
            for (t, _r), pids in self._open.items() if t == tenant
            for pid in pids)

    # -- allocation ---------------------------------------------------------
    def free_slots_available(self, tenant: Hashable = None,
                             regions: list[Hashable] | None = None) -> int:
        """Slots obtainable WITHOUT growing the pool: the tenant's open
        pages' slack plus unclaimed pages (quota-capped), optionally
        restricted to ``regions``."""
        region_ok = (None if regions is None else set(regions))
        slack = sum(
            len(self.pages[pid].free)
            for (t, r), pids in self._open.items()
            if t == tenant and (region_ok is None or r in region_ok)
            for pid in pids)
        fresh = sum(
            len(pids) for r, pids in self._free_pages.items()
            if region_ok is None or r in region_ok)
        rem = self.quota_remaining_pages(tenant)
        if rem is not None:
            fresh = min(fresh, rem)
        return slack + fresh * self.page_rows

    def take_slot(self, tenant: Hashable = None,
                  regions: list[Hashable] | None = None) -> int:
        """Allocate one slot for ``tenant`` (claiming a fresh page when its
        open pages are full). Raises PageQuotaExceeded / RuntimeError when
        nothing is available — callers ensure_free first."""
        region_ok = (None if regions is None else set(regions))
        for key in list(self._open.keys()):
            t, r = key
            if t != tenant or (region_ok is not None and r not in region_ok):
                continue
            pids = self._open[key]
            while pids:
                page = self.pages[pids[-1]]
                if page.free:
                    return self._take_from(page)
                pids.pop()  # page filled up — no longer open
            del self._open[key]
        page = self._claim_page(tenant, region_ok)
        return self._take_from(page)

    def _claim_page(self, tenant: Hashable, region_ok) -> _Page:
        rem = self.quota_remaining_pages(tenant)
        if rem is not None and rem <= 0:
            raise PageQuotaExceeded(
                f"tenant {tenant!r} page quota "
                f"({self.tenant_quota_pages[tenant]} pages x "
                f"{self.page_rows} rows) exhausted")
        for r, pids in self._free_pages.items():
            if pids and (region_ok is None or r in region_ok):
                page = self.pages[pids.pop()]
                page.tenant = tenant
                page.free = list(range(self.page_rows - 1, -1, -1))
                self.tenant_pages[tenant] = \
                    self.tenant_pages.get(tenant, 0) + 1
                self._open.setdefault((tenant, r), []).append(page.pid)
                return page
        raise RuntimeError(
            "no free pages — pool.ensure_free was not called before "
            "take_slot")

    def _take_from(self, page: _Page) -> int:
        off = page.free.pop()
        page.live += 1
        self.live_rows += 1
        return page.base + off

    def release_slot(self, slot: int) -> None:
        page = self.pages[slot // self.page_rows]
        page.free.append(slot - page.base)
        page.live -= 1
        self.live_rows -= 1
        if page.live == 0:
            # page drained: return to the region free list for ANY tenant
            key = (page.tenant, page.region)
            pids = self._open.get(key)
            if pids is not None:
                try:
                    pids.remove(page.pid)
                except ValueError:
                    pass
                if not pids:
                    del self._open[key]
            self.tenant_pages[page.tenant] = \
                self.tenant_pages.get(page.tenant, 1) - 1
            page.tenant = None
            page.free = []
            self._free_pages.setdefault(page.region, []).append(page.pid)
        else:
            # partially-freed page becomes allocatable again for its tenant
            key = (page.tenant, page.region)
            pids = self._open.setdefault(key, [])
            if page.pid not in pids:
                pids.append(page.pid)

    # -- stats --------------------------------------------------------------
    def stats(self) -> dict:
        live_pages = self.n_pages - self.n_free_pages
        return {
            "page_rows": self.page_rows,
            "pages_total": self.n_pages,
            "pages_free": self.n_free_pages,
            "pages_live": live_pages,
            "live_rows": self.live_rows,
            "occupancy": (self.live_rows / (live_pages * self.page_rows)
                          if live_pages else 0.0),
            "tenants": {
                str(t): n for t, n in self.tenant_pages.items() if n > 0},
        }


class Extent:
    """One device allocation of the pool: ``rows`` vector slots starting at
    global row ``base``. Device arrays are established lazily by the owning
    index (ops/knn.py owns the kernels); once established they are only
    ever updated in place (donated scatters) — never copied or re-uploaded
    on growth."""

    __slots__ = ("base", "rows", "vectors", "valid", "scales", "vsq")

    def __init__(self, base: int, rows: int):
        self.base = base
        self.rows = rows
        self.vectors = None
        self.valid = None
        self.scales = None   # int8 slabs only
        self.vsq = None      # int8 slabs only

    @property
    def established(self) -> bool:
        return self.vectors is not None


_LIVE_POOLS: "weakref.WeakSet" = weakref.WeakSet()


def register_pool(pool: Any) -> None:
    """Register a stats source for :func:`live_paged_stats` — anything
    exposing ``stats()`` with the pool-stats keys (DevicePagePool
    registers itself; the mesh-sharded paged index registers too, its
    extents being sharded arrays rather than flat ones)."""
    _LIVE_POOLS.add(pool)


def live_paged_stats() -> dict | None:
    """Aggregate page-occupancy stats over every live pool in the process —
    the /metrics + dashboard feed (None when no paged store exists)."""
    stats = [p.stats() for p in list(_LIVE_POOLS)]
    if not stats:
        return None
    out = {
        "pools": len(stats),
        # pools may carry different page sizes: report the first (the
        # common case is uniform); occupancy sums per-pool live capacity
        "page_rows": stats[0]["page_rows"],
        "pages_total": 0, "pages_free": 0, "pages_live": 0,
        "live_rows": 0, "capacity_rows": 0, "extents": 0,
        "grow_events": 0, "tenants": {},
    }
    live_capacity = 0
    for st in stats:
        for k in ("pages_total", "pages_free", "pages_live", "live_rows",
                  "grow_events"):
            out[k] += st[k]
        out["capacity_rows"] += st["capacity_rows"]
        out["extents"] += st["extents"]
        live_capacity += st["pages_live"] * st["page_rows"]
        for t, n in st["tenants"].items():
            out["tenants"][t] = out["tenants"].get(t, 0) + n
    out["occupancy"] = (out["live_rows"] / live_capacity
                        if live_capacity else 0.0)
    return out


def _aligned_rows(rows: int, rows_per_page: int) -> int:
    """Extent sizing: page multiple, and a chunk multiple past the chunked
    kernel's threshold (the scan reshapes to (C, chunk, D))."""
    from pathway_tpu.ops.knn import _CHUNK_ROWS, _round_up

    rows = _round_up(max(rows, 1), rows_per_page)
    if rows > _CHUNK_ROWS:
        rows = _round_up(rows, _CHUNK_ROWS)
    return rows


class DevicePagePool:
    """Extent list + page allocator for one logical vector store.

    Growth appends an extent at least as large as everything allocated so
    far (doubling → O(log N) extents → O(log N) per-extent search kernels
    and merge width), sized up to cover large single requests.
    """

    def __init__(self, dim: int, *, reserved_space: int = 0,
                 rows_per_page: int | None = None,
                 tenant_quotas: dict[Hashable, int] | None = None,
                 lock=None):
        from pathway_tpu.ops.knn import planned_capacity

        self.dim = int(dim)
        pr = page_rows(rows_per_page)
        quota_p = (
            {t: quota_pages(rows, pr) for t, rows in tenant_quotas.items()}
            if tenant_quotas else None)
        self.allocator = PageAllocator(pr, quota_p)
        self.extents: list[Extent] = []
        self.grow_events = 0
        # the owning index's lock: every mutation happens under it, and
        # stats() (read by the /metrics & dashboard threads) must too —
        # otherwise the allocator's dict iterations can race ingest
        self._owner_lock = lock
        # (established extents, their pages): touched_page_ids()'s answer
        self._touched: tuple = ((), frozenset())
        self._add_extent(_aligned_rows(planned_capacity(reserved_space), pr))
        register_pool(self)

    # -- extents ------------------------------------------------------------
    @property
    def capacity(self) -> int:
        return sum(e.rows for e in self.extents)

    def _add_extent(self, rows: int) -> Extent:
        ext = Extent(self.capacity, rows)
        self.extents.append(ext)
        self.allocator.add_region(
            len(self.extents) - 1, ext.base,
            rows // self.allocator.page_rows)
        return ext

    def grow(self, min_rows: int = 0) -> Extent:
        """Online growth: ONE new extent (device memory established lazily,
        as zeros, on the next flush) — existing extents are not moved,
        copied, re-uploaded or re-quantized."""
        rows = _aligned_rows(max(min_rows, self.capacity),
                             self.allocator.page_rows)
        self.grow_events += 1
        return self._add_extent(rows)

    def ensure_free(self, n: int, tenant: Hashable = None) -> None:
        """Guarantee ``n`` take_slot calls for ``tenant`` succeed."""
        capped = self.allocator.quota_capped_slots(tenant)
        if capped is not None and capped < n:
            raise PageQuotaExceeded(
                f"tenant {tenant!r} needs {n} slots but its page quota "
                f"caps it at {capped} more")
        while self.allocator.free_slots_available(tenant) < n:
            self.grow()

    def reserve_rows(self, n: int, tenant: Hashable = None) -> None:
        """One-shot pre-size for a KNOWN bulk load (snapshot restore,
        bulk re-establish): a single extent covering the whole deficit
        instead of ensure_free's doubling cascade — fewer extents means a
        narrower per-extent search merge afterwards."""
        capped = self.allocator.quota_capped_slots(tenant)
        if capped is not None and capped < n:
            raise PageQuotaExceeded(
                f"tenant {tenant!r} needs {n} slots but its page quota "
                f"caps it at {capped} more")
        deficit = n - self.allocator.free_slots_available(tenant)
        if deficit > 0:
            self.grow(min_rows=deficit)

    # -- slot → extent mapping ---------------------------------------------
    def extent_index_of(self, slot: int) -> int:
        for i, ext in enumerate(self.extents):
            if slot < ext.base + ext.rows:
                return i
        raise IndexError(f"slot {slot} beyond pool capacity {self.capacity}")

    def split_by_extent(self, slots: np.ndarray):
        """Group global slots by extent: yields (extent, local_rows,
        positions) where ``positions`` indexes back into ``slots``. Single-
        extent batches (the common case) yield once with no copy beyond
        the local-offset subtraction."""
        slots = np.asarray(slots, dtype=np.int64)
        for i, ext in enumerate(self.extents):
            in_ext = (slots >= ext.base) & (slots < ext.base + ext.rows)
            if not in_ext.any():
                continue
            pos = np.flatnonzero(in_ext)
            yield ext, (slots[pos] - ext.base), pos

    def touched_page_ids(self) -> frozenset:
        """The page-touch set of a search over this pool *right now*: every
        page of every **established** extent (the per-extent merge scans
        whole extents under their valid masks; extents never established
        hold no rows and are skipped). This is what the semantic result
        cache (engine/result_cache.py) records per entry — an insert into
        a page outside this set at fill time provably landed in device
        memory the entry's candidate scan never read. Callers hold the
        owning index's lock (same contract as every other pool call).
        Every search asks, and the set changes only when an extent is
        established, so it is kept until then (10,240 pages of a slab of
        ten million rows took a search a millisecond to list)."""
        established = tuple((ext.base, ext.rows) for ext in self.extents
                            if ext.established)
        if established != self._touched[0]:
            pr = self.allocator.page_rows
            self._touched = (established, frozenset(
                page for base, rows in established
                for page in range(base // pr, (base + rows) // pr)))
        return self._touched[1]

    def stats(self) -> dict:
        if self._owner_lock is not None:
            with self._owner_lock:
                return self._stats_locked()
        return self._stats_locked()

    def _stats_locked(self) -> dict:
        st = self.allocator.stats()
        st.update({
            "capacity_rows": self.capacity,
            "extents": len(self.extents),
            "grow_events": self.grow_events,
        })
        return st
