"""CompassIndex: the composed index of §IV.A.

Components (one per paper element):
  * ``graph``     — proximity graph over all record vectors (HNSW role).
  * ``centroids`` — IVF layer.  The paper additionally builds a small
    proximity graph over the centroids for "on-demand" cluster ranking
    (§IV.C) because a CPU linear scan over many centroids is expensive.
    On TPU a full centroid scan is a single (B, nlist) x (nlist, d) MXU
    matmul — cheaper than pointer-chasing — so the ranking is computed
    exactly in one shot and consumed *on demand* through a cursor, which
    preserves the paper's semantics (clusters visited in centroid-distance
    order, only as many as needed) while deleting the nprobe-tuning problem
    the same way the paper's cluster graph does.  (DESIGN.md §Adaptation.)
  * ``medoids``   — per-cluster medoid record, used for query-adaptive
    graph entry (the role HNSW's upper layers play on CPU).
  * ``cattrs``    — clustered per-attribute sorted permutations (the
    clustered B+-trees).

``vectors`` / ``attrs`` are stored padded with one sentinel row (index N) so
fixed-shape gathers of sentinel edges read harmless data that is masked out.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.events import timed

from .clustered_attrs import ClusteredAttrs, build_clustered_attrs
from .graph_build import GraphIndex, build_graph
from .kmeans import kmeans
from .planner.stats import AttrStats, build_attr_stats
from .quant.encode import QuantizedVectors


class CompassIndex(NamedTuple):
    vectors: jax.Array  # (N + 1, d) padded
    attrs: jax.Array  # (N + 1, A) padded (sentinel row fails all predicates)
    graph: GraphIndex  # neighbors (N, M), entry (global medoid fallback)
    centroids: jax.Array  # (nlist, d)
    medoids: jax.Array  # (nlist,) int32 — medoid record id per cluster
    cattrs: ClusteredAttrs
    # per-cluster/per-attribute equi-depth histograms for the cost-based
    # planner; None on indices built before the planner existed (the
    # planner then refuses to run — CompassParams(planner=True) raises).
    astats: AttrStats | None = None
    # tombstone mask for the mutable-index subsystem (core/mutable): (N + 1,)
    # bool, False == deleted/superseded.  A dead record stays in the graph
    # and the sorted runs as a routing node — traversal still flows through
    # it — but the engine never admits it to the filtered result queue
    # (state.visit / the PREFILTER adoption both AND with this mask).  None
    # on a plain immutable index: zero cost until mutability is in play.
    live: jax.Array | None = None
    # product-quantized tier (core/quant): uint8 codes + frozen per-subspace
    # codebooks, attached by ``quantize_index``.  Scored through the ADC
    # tables when ``CompassParams.quant`` is set; ``None`` (the default)
    # keeps every exact-search program bitwise identical to pre-quant code
    # (trace-time branch on the pytree treedef, like ``live``).
    qvecs: QuantizedVectors | None = None

    @property
    def n_records(self) -> int:
        return self.vectors.shape[0] - 1

    @property
    def dim(self) -> int:
        return self.vectors.shape[1]

    @property
    def n_attrs(self) -> int:
        return self.attrs.shape[1]

    @property
    def nlist(self) -> int:
        return self.centroids.shape[0]


@dataclasses.dataclass(frozen=True)
class BuildConfig:
    m: int = 16  # graph max out-degree
    nlist: int = 64  # IVF cluster count
    kmeans_iters: int = 10
    nn_descent_rounds: int = 1
    prune_alpha: float = 1.2
    metric: str = "l2"
    seed: int = 0
    hist_bins: int = 64  # global equi-depth histogram bins per attribute
    cluster_hist_bins: int = 8  # per-cluster equi-depth bins per attribute


def cluster_medoids(
    vectors: np.ndarray,
    assign: np.ndarray,
    centroids: np.ndarray,
    fallback: int,
    metric: str = "l2",
) -> np.ndarray:
    """Per-cluster medoid (member closest to its centroid), computed as one
    segmented argmin instead of an O(nlist) host loop: every record scores
    against its *own* centroid (one gather + row-wise reduction), then a
    single ``lexsort`` by (cluster, distance) makes each cluster's first row
    its medoid.  Compaction re-derives medoids on every delta fold, so this
    is on the write path, not just index build.

    Empty clusters get ``fallback`` (the graph entry point).
    """
    vectors = np.asarray(vectors, np.float32)
    assign = np.asarray(assign, np.int64)
    nlist = centroids.shape[0]
    own = centroids[assign]  # (n, d) each record's centroid
    xy = np.einsum("nd,nd->n", vectors, own)
    if metric == "l2":
        d = np.einsum("nd,nd->n", vectors, vectors) - 2.0 * xy
    else:
        d = -xy
    perm = np.lexsort((d, assign))  # primary: cluster, secondary: distance
    a_sorted = assign[perm]
    first = np.r_[True, a_sorted[1:] != a_sorted[:-1]]
    medoids = np.full((nlist,), fallback, np.int32)
    medoids[a_sorted[first]] = perm[first]
    return medoids


def build_index(vectors: np.ndarray, attrs: np.ndarray, cfg: BuildConfig = BuildConfig()) -> CompassIndex:
    vectors = np.asarray(vectors, np.float32)
    attrs = np.asarray(attrs, np.float32)
    if cfg.metric == "cos":
        # cosine == inner product over unit rows: normalize the corpus once
        # here and build everything (graph, kmeans, medoids) as "ip"; the
        # driver normalizes queries at search entry (driver.compass_search)
        from .distances import normalize_rows

        vectors = np.asarray(normalize_rows(vectors))
        cfg = dataclasses.replace(cfg, metric="ip")
    n, d = vectors.shape
    # the IVF k-means compiles on a worker thread while the graph builds;
    # it is the same program the plain call would compile
    with ThreadPoolExecutor(1) as ex:
        ivf_kmeans = ex.submit(lambda: kmeans.lower(
            jax.ShapeDtypeStruct((n, d), jnp.float32), cfg.nlist, iters=cfg.kmeans_iters,
            seed=cfg.seed, metric=cfg.metric).compile())
        graph = build_graph(
            vectors,
            cfg.m,
            nn_descent_rounds=cfg.nn_descent_rounds,
            prune_alpha=cfg.prune_alpha,
            metric=cfg.metric,
            seed=cfg.seed,
        )
        ivf_kmeans = ivf_kmeans.result()
    # each phase's wall time is an ``index_build_phase`` event (obs/events)
    with timed("index_build_phase", phase="ivf_kmeans", n_rows=n):
        km = ivf_kmeans(jnp.asarray(vectors), seed=cfg.seed)
        centroids = np.asarray(km.centroids)
        assign = np.asarray(km.assignments)
    with timed("index_build_phase", phase="runs_and_stats", n_rows=n):
        medoids = cluster_medoids(vectors, assign, centroids, int(graph.entry), cfg.metric)
        cattrs = build_clustered_attrs(attrs, assign, cfg.nlist)
        astats = build_attr_stats(
            attrs, assign, cfg.nlist, n_bins=cfg.hist_bins, n_cluster_bins=cfg.cluster_hist_bins
        )
    # Sentinel padding rows. Attr sentinel = +inf fails every closed interval
    # whose hi is finite; predicates with hi = +inf (one-sided) are protected
    # by the validity masks in search, this is defence-in-depth.
    vpad = np.concatenate([vectors, np.zeros((1, d), np.float32)], 0)
    apad = np.concatenate([attrs, np.full((1, attrs.shape[1]), np.inf, np.float32)], 0)
    return CompassIndex(
        jnp.asarray(vpad),
        jnp.asarray(apad),
        graph,
        jnp.asarray(centroids),
        jnp.asarray(medoids),
        cattrs,
        astats,
    )
