"""KNN inner indexes (reference: stdlib/indexing/nearest_neighbors.py —
BruteForceKnn:141, USearchKnn:48, LshKnn:221).

All variants run on the TPU brute-force slab (ops/knn.py): exact search at
matmul speed supersedes the reference's approximate engines at these scales
(USearch HNSW / LSH exist in the reference to avoid CPU O(N·d) scans; one
MXU matmul over an HBM slab makes the exact scan the fast path).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

from pathway_tpu.internals import expression as ex
from pathway_tpu.ops.knn import BruteForceKnnIndex, KnnMetric
from pathway_tpu.stdlib.indexing.data_index import DataIndex, InnerIndex


class BruteForceKnnMetricKind:
    L2SQ = KnnMetric.L2SQ
    COS = KnnMetric.COS


@dataclass
class BruteForceKnnFactory:
    """Engine-side index factory (reference: ExternalIndexFactory,
    src/external_integration/mod.rs:46 — one instance per worker).

    Scaling is device-mesh-first: with ``mesh`` set (or ``mesh='auto'``
    and >1 device on the data axis) the factory builds the mesh-sharded
    index (parallel/sharded_knn.py — slab split over ICI, per-shard top-k
    merge), the TPU-native counterpart of the reference's per-worker index
    instances. ``dtype='bfloat16'`` halves slab bytes AND scan time
    (10M x 384 fits one chip); ``dtype='int8'`` halves them again
    (per-row symmetric quantization on device, host mirror exact f32 —
    see ops/knn.py)."""

    dimensions: int | None = None
    reserved_space: int = 1024
    metric: KnnMetric = KnnMetric.L2SQ
    embedder: Any = None
    mesh: Any = None
    dtype: str = "float32"
    # False forces the vector-input engine index even for device-capable
    # embedders (set by DataIndex when a query-embedder override is in
    # play — the fused text path could not honor it)
    fuse: bool = True
    # this index's page-allocator tenant tag + per-tenant row quotas
    # (rounded UP to whole pages; PWT111 flags non-page-aligned quotas and
    # quota sums past device HBM)
    tenant: Any = None
    tenant_quotas: dict | None = None

    def build(self):
        dim = self.dimensions
        if dim is None:
            dim = _probe_embedder_dimension(self.embedder)
        mesh = self.mesh
        if mesh == "auto":
            from pathway_tpu.parallel.mesh import DATA_AXIS, get_mesh

            m = get_mesh()
            mesh = m if m is not None and int(
                m.shape.get(DATA_AXIS, 1)) > 1 else None
        if mesh is not None:
            from pathway_tpu.parallel.sharded_knn import ShardedKnnIndex

            return ShardedKnnIndex(dim, mesh=mesh,
                                   reserved_space=self.reserved_space,
                                   metric=self.metric, dtype=self.dtype,
                                   tenant=self.tenant,
                                   tenant_quotas=self.tenant_quotas)
        inner = BruteForceKnnIndex(
            dim, reserved_space=self.reserved_space, metric=self.metric,
            dtype=self.dtype, tenant=self.tenant,
            tenant_quotas=self.tenant_quotas)
        # device-capable embedder: the engine index takes raw text and
        # embeds on-chip; embeddings never round-trip the host. The gate
        # must mirror BruteForceKnn.embeds_internally exactly — that
        # property decides whether the DataIndex feeds text or vectors
        # (self.mesh, not the resolved mesh: 'auto' may resolve to None
        # here while the planner already chose the vector column)
        if self.fuse and self.mesh is None and hasattr(
                self.embedder, "encode_batch_device"):
            from pathway_tpu.ops.knn import DeviceEmbeddingKnnIndex

            return DeviceEmbeddingKnnIndex(self.embedder, inner)
        return inner


def _probe_embedder_dimension(embedder) -> int:
    if embedder is None:
        raise ValueError("dimensions required when no embedder is given")
    from pathway_tpu.xpacks.llm._utils import get_embedding_dimension

    return get_embedding_dimension(embedder)


class BruteForceKnn(InnerIndex):
    def __init__(self, data_column: ex.ColumnReference,
                 metadata_column: ex.ColumnExpression | None = None, *,
                 dimensions: int | None = None, reserved_space: int = 1024,
                 metric: KnnMetric = KnnMetric.L2SQ, embedder: Any = None,
                 mesh: Any = None, dtype: str = "float32",
                 tenant: Any = None, tenant_quotas: dict | None = None):
        super().__init__(data_column, metadata_column)
        self.dimensions = dimensions
        self.reserved_space = reserved_space
        self.metric = metric
        self.embedder = embedder
        self.mesh = mesh
        self.dtype = dtype
        self.tenant = tenant
        self.tenant_quotas = tenant_quotas

    def factory(self) -> BruteForceKnnFactory:
        return BruteForceKnnFactory(
            dimensions=self.dimensions, reserved_space=self.reserved_space,
            metric=self.metric, embedder=self.embedder, mesh=self.mesh,
            dtype=self.dtype, tenant=self.tenant,
            tenant_quotas=self.tenant_quotas)

    @property
    def query_embedder(self):
        return self.embedder

    @property
    def embeds_internally(self) -> bool:
        """True when the engine index embeds raw text on device itself
        (DeviceEmbeddingKnnIndex) — the DataIndex then skips the UDF
        embedding column entirely for both data and queries."""
        return self.mesh is None and hasattr(self.embedder,
                                             "encode_batch_device")


@dataclass
class UsearchEngineIndexFactory:
    """Engine-side factory building the native HNSW
    (native/hnsw_index.cpp; reference: usearch_integration.rs:20
    USearchKNNIndexFactory). Sublinear search for corpora beyond one
    chip's HBM or CPU-only deployments; the TPU slab (BruteForceKnn)
    remains the exact fast path at in-HBM scales. (Named distinctly from
    retrievers.UsearchKnnFactory, the user-facing retriever factory.)"""

    dimensions: int | None = None
    reserved_space: int = 1024
    metric: KnnMetric = KnnMetric.COS
    connectivity: int = 16
    expansion_add: int = 128
    expansion_search: int = 192
    embedder: Any = None

    def build(self):
        from pathway_tpu.ops.hnsw import HnswIndex

        dim = self.dimensions
        if dim is None:
            dim = _probe_embedder_dimension(self.embedder)
        return HnswIndex(
            dim, metric=self.metric,
            connectivity=self.connectivity or 16,
            expansion_add=self.expansion_add or 128,
            expansion_search=self.expansion_search or 192)


class USearchKnn(BruteForceKnn):
    """The reference's USearchKnn: a REAL HNSW index (native C++ engine,
    native/hnsw_index.cpp) — approximate, sublinear search with the
    usearch parameter surface (connectivity / expansion_add /
    expansion_search)."""

    def __init__(self, data_column, metadata_column=None, *, dimensions=None,
                 reserved_space: int = 1024, metric=KnnMetric.COS,
                 connectivity: int = 0, expansion_add: int = 0,
                 expansion_search: int = 0, embedder=None):
        if isinstance(metric, str):
            metric = {"cos": KnnMetric.COS, "l2sq": KnnMetric.L2SQ}.get(
                metric.lower(), KnnMetric.COS)
        super().__init__(data_column, metadata_column, dimensions=dimensions,
                         reserved_space=reserved_space, metric=metric,
                         embedder=embedder)
        self.connectivity = connectivity
        self.expansion_add = expansion_add
        self.expansion_search = expansion_search

    def factory(self) -> UsearchEngineIndexFactory:
        return UsearchEngineIndexFactory(
            dimensions=self.dimensions, reserved_space=self.reserved_space,
            metric=self.metric, connectivity=self.connectivity,
            expansion_add=self.expansion_add,
            expansion_search=self.expansion_search, embedder=self.embedder)

    @property
    def embeds_internally(self) -> bool:
        # the native HNSW is a host-side index: it needs real vectors in
        # its add path, so the UDF embedding column stays
        return False


class LshKnn(BruteForceKnn):
    """API-compatible with the reference's LshKnn (random-projection LSH,
    stdlib/ml/classifiers/_knn_lsh.py); executes as the exact TPU scan."""

    def __init__(self, data_column, metadata_column=None, *, dimensions=None,
                 n_or: int = 20, n_and: int = 10, bucket_length: float = 10.0,
                 distance_type: str = "euclidean", reserved_space: int = 1024,
                 embedder=None):
        metric = KnnMetric.COS if distance_type == "cosine" else KnnMetric.L2SQ
        super().__init__(data_column, metadata_column, dimensions=dimensions,
                         reserved_space=reserved_space, metric=metric,
                         embedder=embedder)
