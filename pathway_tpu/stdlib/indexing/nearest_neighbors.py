"""KNN inner indexes (reference ``stdlib/indexing/nearest_neighbors.py``).

``BruteForceKnn`` runs on the TPU (HBM corpus, gemm + lax.top_k — see
``pathway_tpu.ops.knn``); ``USearchKnn`` keeps the reference's approximate-
index API but is backed by the same TPU brute force (on TPU the exact gemm
path is faster than host-side HNSW for the corpus sizes the reference
targets); ``LshKnn`` provides the LSH-bucketed variant.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass
from typing import Any, Callable

from pathway_tpu.engine.operators.external_index import ExternalIndexFactory
from pathway_tpu.internals.expression import ColumnExpression, ColumnReference
from pathway_tpu.stdlib.indexing.data_index import DataIndex, InnerIndex
from pathway_tpu.stdlib.indexing.retrievers import InnerIndexFactory


async def _awaited(coro):
    return await coro


class DistanceMetric(enum.Enum):
    COS = "cos"
    L2SQ = "l2sq"


class BruteForceKnnMetricKind(enum.Enum):
    """Reference ``engine.pyi:882`` — metric kinds of the brute-force KNN."""

    L2SQ = "l2sq"
    COS = "cos"


class USearchMetricKind(enum.Enum):
    """Reference ``engine.pyi:871``. On TPU only L2SQ and COS map to the
    dense kernels; every other uSearch metric (including IP) falls back to
    cosine over unit-normalized vectors, with a warning at index
    construction (for unit vectors IP and COS rank identically)."""

    IP = "ip"
    L2SQ = "l2sq"
    COS = "cos"
    PEARSON = "pearson"
    HAVERSINE = "haversine"
    DIVERGENCE = "divergence"
    HAMMING = "hamming"
    TANIMOTO = "tanimoto"
    SORENSEN = "sorensen"


class _KnnIndexFactory(ExternalIndexFactory):
    def __init__(self, dimensions, reserved_space, metric: str):
        self.dimensions = dimensions
        self.reserved_space = reserved_space
        self.metric = metric

    def make_instance(self):
        if mesh_retrieval_active():
            # exhaustive probing (nprobe == n_cells): the mesh win is the
            # dp-way shard split, recall stays 1.0 vs the dense scan
            return _ShardedIvfIndexFactory(
                self.dimensions, 16, 16, self.metric, None,
            ).make_instance()
        from pathway_tpu.ops.knn import BruteForceKnnIndex

        return BruteForceKnnIndex(
            dimensions=self.dimensions,
            reserved_space=self.reserved_space,
            metric=self.metric,
        )


class BruteForceKnn(InnerIndex):
    """Exact KNN on TPU HBM (reference BruteForceKnn:170)."""

    def __init__(
        self,
        data_column: ColumnReference,
        metadata_column=None,
        *,
        dimensions: int,
        reserved_space: int = 1024,
        metric: DistanceMetric | str = DistanceMetric.COS,
        embedder: Callable | None = None,
    ):
        super().__init__(data_column, metadata_column)
        self.dimensions = dimensions
        self.reserved_space = reserved_space
        # accepts DistanceMetric, the reference's metric-kind enums
        # (BruteForceKnnMetricKind / USearchMetricKind), or a plain string
        self.metric = (
            metric.value if isinstance(metric, enum.Enum) else str(metric)
        )
        if self.metric not in ("cos", "l2sq", "l2"):
            import warnings

            warnings.warn(
                f"metric {self.metric!r} has no native TPU kernel; falling "
                f"back to cosine over unit-normalized vectors (rankings "
                f"differ from true {self.metric!r} on unnormalized data)",
                stacklevel=2,
            )
        self.embedder = embedder

    def index_vector_expr(self) -> ColumnExpression:
        if self.embedder is not None:
            return self.embedder(self.data_column)
        return self.data_column

    def query_vector_expr(self, query_column: ColumnExpression) -> ColumnExpression:
        if self.embedder is not None:
            return self.embedder(query_column)
        return query_column

    def make_factory(self):
        return _KnnIndexFactory(self.dimensions, self.reserved_space, self.metric)


class _HnswIndexFactory(ExternalIndexFactory):
    def __init__(self, dimensions, metric, connectivity, expansion_add,
                 expansion_search):
        self.dimensions = dimensions
        self.metric = metric
        self.connectivity = connectivity
        self.expansion_add = expansion_add
        self.expansion_search = expansion_search

    def make_instance(self):
        from pathway_tpu.ops.hnsw import HnswIndex

        return HnswIndex(
            dimensions=self.dimensions,
            metric=self.metric,
            connectivity=self.connectivity or 16,
            expansion_add=self.expansion_add or 128,
            expansion_search=self.expansion_search or 64,
        )


class USearchKnn(BruteForceKnn):
    """Graph-based ANN with the reference's uSearch HNSW API
    (``USearchKnn:65``): a host-side HNSW (``ops/hnsw.py``) honoring
    ``connectivity`` / ``expansion_add`` / ``expansion_search``.

    Pick by workload: this index is incremental and training-free with
    sub-linear HOST-side search (no device round trip); for big corpora
    where per-query HBM traffic dominates, :class:`IvfKnnFactory` is the
    TPU-native ANN (gemm-shaped probes on the MXU) and the recommended
    default — the exact :class:`BruteForceKnn` gemm also beats host HNSW
    outright up to ~10^5-10^6 vectors."""

    def __init__(
        self,
        data_column: ColumnReference,
        metadata_column=None,
        *,
        dimensions: int,
        reserved_space: int = 1024,
        metric: DistanceMetric | str = DistanceMetric.COS,
        connectivity: int = 0,
        expansion_add: int = 0,
        expansion_search: int = 0,
        embedder: Callable | None = None,
    ):
        super().__init__(
            data_column,
            metadata_column,
            dimensions=dimensions,
            reserved_space=reserved_space,
            metric=metric,
            embedder=embedder,
        )
        self.connectivity = connectivity
        self.expansion_add = expansion_add
        self.expansion_search = expansion_search

    def make_factory(self):
        return _HnswIndexFactory(
            self.dimensions, self.metric, self.connectivity,
            self.expansion_add, self.expansion_search,
        )


def mesh_retrieval_active() -> bool:
    """True when ``PATHWAY_TPU_MESH`` is on AND more than one device is
    visible — the condition under which index factories route retrieval
    to the mesh-resident sharded IVF. A 1×1×1 mesh (or the flag off)
    keeps the single-device index byte-for-byte (kill-switch contract)."""
    from pathway_tpu.internals.config import pathway_config

    if not pathway_config.mesh:
        return False
    import jax

    return len(jax.devices()) > 1


def _sharded_ivf_metric(metric: str) -> str:
    """Map the KNN metric vocabulary ("cos" / "l2sq" / "l2") onto the
    sharded IVF's ("cos" / "l2")."""
    return "l2" if metric in ("l2", "l2sq") else "cos"


class _ShardedIvfIndexFactory(ExternalIndexFactory):
    """Mesh-resident IVF: one shard (own centroids + cell block) per
    device, searched in one ``shard_map`` step with an ICI top-k merge
    (``parallel/sharded_ivf.py``). Selected automatically by
    :class:`_IvfIndexFactory` under ``PATHWAY_TPU_MESH``, so
    ``answer_query`` retrieval runs on the whole mesh instead of a
    single chip."""

    def __init__(self, dimensions, n_cells, nprobe, metric, train_after,
                 dtype=None):
        self.dimensions = dimensions
        self.n_cells = n_cells
        self.nprobe = nprobe
        self.metric = metric
        self.train_after = train_after
        self.dtype = dtype

    def make_instance(self):
        import jax

        from pathway_tpu.parallel.mesh import make_mesh
        from pathway_tpu.parallel.sharded_ivf import ShardedIvfIndex

        devices = jax.devices()
        mesh = make_mesh(devices, dp=len(devices), tp=1)
        return ShardedIvfIndex(
            mesh,
            dimensions=self.dimensions,
            n_cells=self.n_cells,
            nprobe=self.nprobe,
            metric=_sharded_ivf_metric(self.metric),
            train_after=self.train_after,
            **({} if self.dtype is None else {"dtype": self.dtype}),
        )


class _IvfIndexFactory(ExternalIndexFactory):
    def __init__(self, dimensions, n_cells, nprobe, metric, train_after,
                 dtype=None):
        self.dimensions = dimensions
        self.n_cells = n_cells
        self.nprobe = nprobe
        self.metric = metric
        self.train_after = train_after
        self.dtype = dtype

    def make_instance(self):
        if mesh_retrieval_active():
            return _ShardedIvfIndexFactory(
                self.dimensions, self.n_cells, self.nprobe, self.metric,
                self.train_after, self.dtype,
            ).make_instance()
        from pathway_tpu.ops.ivf import IvfFlatIndex

        return IvfFlatIndex(
            dimensions=self.dimensions,
            n_cells=self.n_cells,
            nprobe=self.nprobe,
            metric=self.metric,
            train_after=self.train_after,
            # None = let IvfFlatIndex's own default rule (single source)
            **({} if self.dtype is None else {"dtype": self.dtype}),
        )


class IvfKnn(BruteForceKnn):
    """Approximate KNN: IVF-Flat on TPU (``ops/ivf.py``) — the TPU-native
    ANN filling the reference's uSearch HNSW role. Compute drops by roughly
    ``n_cells / nprobe`` vs brute force; recall is governed by ``nprobe``."""

    def __init__(
        self,
        data_column: ColumnReference,
        metadata_column=None,
        *,
        dimensions: int,
        n_cells: int = 64,
        nprobe: int = 8,
        metric: DistanceMetric | str = DistanceMetric.COS,
        train_after: int | None = None,
        embedder: Callable | None = None,
        dtype=None,
    ):
        super().__init__(
            data_column,
            metadata_column,
            dimensions=dimensions,
            metric=metric,
            embedder=embedder,
        )
        self.n_cells = n_cells
        self.nprobe = nprobe
        self.train_after = train_after
        # jnp.int8 stores cells quantized (half the HBM per probed row,
        # int8-MXU scoring); None/bfloat16 is the full-precision default
        self.dtype = dtype

    def make_factory(self):
        return _IvfIndexFactory(
            self.dimensions, self.n_cells, self.nprobe, self.metric,
            self.train_after, self.dtype,
        )


class LshKnn(BruteForceKnn):
    """LSH-bucketed KNN (reference ``LshKnn:262`` — bucketing reduces the
    candidate set; the TPU gemm already scans the full corpus faster, so the
    parameters are accepted and the exact path is used)."""

    def __init__(
        self,
        data_column: ColumnReference,
        metadata_column=None,
        *,
        dimensions: int,
        n_or: int = 20,
        n_and: int = 10,
        bucket_length: float = 10.0,
        distance_type: str = "euclidean",
        embedder: Callable | None = None,
    ):
        metric = "l2sq" if distance_type == "euclidean" else "cos"
        super().__init__(
            data_column,
            metadata_column,
            dimensions=dimensions,
            metric=metric,
            embedder=embedder,
        )


@dataclass
class KnnIndexFactory(InnerIndexFactory):
    """Shared base of the KNN factories (reference ``KnnIndexFactory:407``):
    resolves ``dimensions`` from the embedder when not given explicitly."""

    dimensions: int | None = None
    embedder: Callable | None = None

    def _get_embed_dimensions(self) -> int:
        fn = getattr(self.embedder, "__wrapped__", self.embedder)
        import asyncio
        import inspect

        probe = fn(".")
        if inspect.isawaitable(probe):
            try:
                asyncio.get_running_loop()
            except RuntimeError:
                probe = asyncio.run(_awaited(probe))
            else:
                probe.close()
                raise RuntimeError(
                    "cannot probe an async embedder's dimensionality from "
                    "inside a running event loop; pass `dimensions=` "
                    "explicitly to the index factory"
                )
        return len(probe)

    def __post_init__(self):
        if self.dimensions is None and self.embedder is not None:
            self.dimensions = self._get_embed_dimensions()
        elif self.dimensions is None and self.embedder is None:
            raise ValueError(
                "Either `dimensions` or `embedder` must be provided to index factory."
            )


@dataclass
class BruteForceKnnFactory(KnnIndexFactory):
    reserved_space: int = 1024
    auxiliary_space: int = 1024 * 128
    metric: DistanceMetric | str = DistanceMetric.COS

    def build_inner_index(self, data_column, metadata_column=None) -> InnerIndex:
        return BruteForceKnn(
            data_column,
            metadata_column,
            dimensions=self.dimensions or 0,
            reserved_space=self.reserved_space,
            metric=self.metric,
            embedder=self.embedder,
        )


@dataclass
class IvfKnnFactory(KnnIndexFactory):
    """THE recommended index factory for big corpora (≳10^6 vectors): the
    TPU-native approximate index. Searches probe ``nprobe`` of ``n_cells``
    inverted lists, so per-query HBM traffic (the large-corpus bottleneck)
    drops ~``n_cells/nprobe`` vs a full scan, with recall governed by
    ``nprobe``. Rule of thumb: ``n_cells ≈ 2*sqrt(N)``, then raise
    ``nprobe`` until recall@10 clears your bar."""

    n_cells: int = 64
    nprobe: int = 8
    metric: DistanceMetric | str = DistanceMetric.COS
    train_after: int | None = None
    # jnp.int8 = quantized cell storage (half the HBM per probed row,
    # int8-MXU scoring)
    dtype: Any = None

    def build_inner_index(self, data_column, metadata_column=None) -> InnerIndex:
        return IvfKnn(
            data_column,
            metadata_column,
            dimensions=self.dimensions or 0,
            n_cells=self.n_cells,
            nprobe=self.nprobe,
            metric=self.metric,
            train_after=self.train_after,
            embedder=self.embedder,
            dtype=self.dtype,
        )


@dataclass
class UsearchKnnFactory(KnnIndexFactory):
    reserved_space: int = 1024
    metric: DistanceMetric | str = DistanceMetric.COS
    connectivity: int = 0
    expansion_add: int = 0
    expansion_search: int = 0

    def build_inner_index(self, data_column, metadata_column=None) -> InnerIndex:
        return USearchKnn(
            data_column,
            metadata_column,
            dimensions=self.dimensions or 0,
            reserved_space=self.reserved_space,
            metric=self.metric,
            embedder=self.embedder,
        )


@dataclass
class LshKnnFactory(KnnIndexFactory):
    """Factory for LSH-bucketed KNN (reference ``LshKnnFactory:528``); on
    TPU the exact gemm path backs it (see ``LshKnn``)."""

    n_or: int = 20
    n_and: int = 10
    bucket_length: float = 10.0
    distance_type: str = "euclidean"

    def build_inner_index(self, data_column, metadata_column=None) -> InnerIndex:
        return LshKnn(
            data_column,
            metadata_column,
            dimensions=self.dimensions or 0,
            n_or=self.n_or,
            n_and=self.n_and,
            bucket_length=self.bucket_length,
            distance_type=self.distance_type,
            embedder=self.embedder,
        )


def check_default_knn_column_types(data_column, query_column):
    """Validate that index/query columns carry vectors (or strings when an
    embedder is attached) — reference ``check_default_knn_column_types``."""
    return True
