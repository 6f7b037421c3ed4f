"""Proximity-graph construction, TPU-native.

Hardware adaptation (DESIGN.md §Adaptation): HNSW's *incremental insertion*
is inherently sequential pointer-chasing — each insert greedily walks the
graph built so far.  That algorithm does not map to a systolic machine, but
the paper itself notes (§IV.D "Flexibility") that the proximity graph is an
interchangeable component ("HNSW can be replaced with a different proximity
graph algorithm like NSG").  We therefore build a *flat* navigable graph
(NSG/Vamana-family) with fully batched, MXU-friendly steps:

  1. coarse k-means over the corpus,
  2. per-cluster candidate pools from the ``link`` nearest clusters;
     exact top-R neighbours inside each pool        (dense matmuls),
  3. optional NN-descent rounds (neighbours-of-neighbours refinement,
     batched gathers + matmuls),
  4. vectorized occlusion ("robust") pruning à la HNSW heuristic / Vamana,
  5. reverse-edge augmentation to a max out-degree M,
  6. medoid entry point (replaces HNSW's upper layers; identical role:
     a navigable, query-independent entry).

Search-time traversal (``repro.core.engine``) is byte-for-byte the paper's
best-first loop and does not care which construction produced the graph.
"""
from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.events import timed

from .distances import pairwise
from .kmeans import kmeans


#: threads of the host-side candidate pools
_HOST_WORKERS = min(16, os.cpu_count() or 1)


@functools.cache
def _blas_set_threads():
    """OpenBLAS's ``openblas_set_num_threads_local`` of the library numpy
    calls (a numpy wheel's ``numpy.libs``), or None: it sets the BLAS
    threads of the process and returns the count it replaces.  A product's
    values do not depend on that count (threads split the rows and columns
    of the output, never a sum), so it changes the speed only."""
    import ctypes
    import glob

    libs = os.path.join(os.path.dirname(np.__file__), os.pardir, "numpy.libs")
    for path in sorted(glob.glob(os.path.join(libs, "*openblas*"))):
        try:
            fn = ctypes.CDLL(path).openblas_set_num_threads_local
        except (OSError, AttributeError):
            continue
        fn.argtypes, fn.restype = [ctypes.c_int], ctypes.c_int
        return fn
    return None


class GraphIndex(NamedTuple):
    neighbors: jax.Array  # (N, M) int32; sentinel == N for missing edges
    entry: jax.Array  # () int32 medoid entry point

    @property
    def n_nodes(self) -> int:
        return self.neighbors.shape[0]

    @property
    def degree(self) -> int:
        return self.neighbors.shape[1]


def _topk_neighbors_in_pools(
    x: np.ndarray,
    assign: np.ndarray,
    centroids: np.ndarray,
    n_candidates: int,
    link: int,
    metric: str,
) -> np.ndarray:
    """Initial candidate lists: exact top-k inside cluster neighbourhoods."""
    n = x.shape[0]
    kc = centroids.shape[0]
    link = min(link, kc)
    cdist = np.asarray(pairwise(jnp.asarray(centroids), jnp.asarray(centroids), metric))
    near_clusters = np.argsort(cdist, axis=1)[:, :link]  # (kc, link)
    members: list[np.ndarray] = [np.where(assign == c)[0] for c in range(kc)]
    cand = np.full((n, n_candidates), n, np.int32)

    # Pure numpy: cluster shapes vary per iteration, which would retrigger
    # XLA compilation every cluster; at these pool sizes BLAS is plenty.
    # Clusters run on a thread pool over one BLAS thread each (numpy and
    # BLAS release the GIL; many callers of a multi-threaded BLAS contend
    # and run slower than one); each writes only its own members' rows, so
    # the order never shows.  Where the BLAS threads cannot be set they run
    # in turn.
    x2 = (x * x).sum(1)

    def one(c):
        mem = members[c]
        if mem.size == 0:
            return
        pool = np.concatenate([members[cc] for cc in near_clusters[c]])
        xy = x[mem] @ x[pool].T
        if metric == "l2":
            d = x2[mem][:, None] + x2[pool][None, :] - 2.0 * xy
        else:
            d = -xy
        # mask self
        d[mem[:, None] == pool[None, :]] = np.inf
        k = min(n_candidates, pool.size)
        idx = np.argpartition(d, kth=k - 1, axis=1)[:, :k]
        srt = np.take_along_axis(d, idx, axis=1).argsort(axis=1)
        idx = np.take_along_axis(idx, srt, axis=1)
        cand[mem, :k] = pool[idx]

    set_threads = _blas_set_threads()
    if set_threads is None:
        for c in range(kc):
            one(c)
        return cand
    blas_threads = set_threads(1)
    try:
        with ThreadPoolExecutor(_HOST_WORKERS) as ex:
            list(ex.map(one, range(kc)))
    finally:
        set_threads(blas_threads)
    return cand


#: rows per NN-descent and prune block at widths up to ``_BLOCK_WIDTH``
_MAX_BLOCK_ROWS = 1024
_BLOCK_WIDTH = 128
#: reached rows a connectivity-repair round samples, up to ``_BLOCK_WIDTH``-d
_REPAIR_SAMPLE = 4096


def _rows_within(max_rows: int, d: int) -> int:
    """``max_rows`` up to ``_BLOCK_WIDTH``-d, else the largest power of two
    whose rows of width ``d`` hold no more values than ``max_rows`` rows of
    ``_BLOCK_WIDTH``: the work and bytes per row grow with the width, so a
    block of rows shrinks to keep them."""
    rows = max_rows
    while rows > 1 and rows * d > max_rows * _BLOCK_WIDTH:
        rows //= 2
    return rows


def _block_rows(d: int) -> int:
    """Rows per NN-descent and prune block at width ``d``: 1024 up to 128-d,
    else no more bytes in the gathered ``(rows, C, d)`` block than 1024 rows
    of a 128-d corpus hold (a 960-d corpus takes 128).  Every row of a block
    is computed on its own, so the block size never changes the graph; it
    bounds the device memory a round takes beside the table."""
    return _rows_within(_MAX_BLOCK_ROWS, d)


def _blocked(block, cand: jax.Array, bs: int, out_cols: int) -> jax.Array:
    """``block(node_ids, cand_blk)`` over the rows of ``cand`` in blocks of
    ``bs``, padded with sentinel rows; the padding's rows are dropped."""
    n, r = cand.shape
    pad = (-n) % bs
    ids = jnp.arange(n + pad, dtype=jnp.int32)
    cand_p = jnp.concatenate([cand, jnp.full((pad, r), n, jnp.int32)], 0)
    out = jax.lax.map(
        lambda args: block(*args), (ids.reshape(-1, bs), cand_p.reshape(-1, bs, r))
    )
    return out.reshape(-1, out_cols)[:n]


@functools.partial(jax.jit, static_argnames=("metric", "bs"))
def _nn_descent_round(xp: jax.Array, cand: jax.Array, metric: str, bs: int) -> jax.Array:
    """One neighbours-of-neighbours refinement round (batched) over the
    sentinel-padded table ``xp`` (N + 1, d), in blocks of ``bs`` rows."""
    n, r = cand.shape
    sentinel = n

    def block(node_ids, cand_blk):
        nbrs2 = cand.at[jnp.clip(cand_blk, 0, n - 1)].get(mode="clip")  # (b, r, r)
        nbrs2 = jnp.where(cand_blk[:, :, None] >= n, sentinel, nbrs2)
        pool = jnp.concatenate([cand_blk, nbrs2.reshape(cand_blk.shape[0], -1)], 1)
        vecs = xp[jnp.clip(pool, 0, n)]  # (b, C, d)
        q = xp[node_ids]  # (b, d); the padding's rows are dropped
        diff = vecs - q[:, None, :]
        if metric == "l2":
            d = jnp.sum(diff * diff, -1)
        else:
            d = -jnp.einsum("bcd,bd->bc", vecs, q)
        invalid = (pool >= n) | (pool == node_ids[:, None])
        d = jnp.where(invalid, jnp.inf, d)
        # Dedup in O(C log C): identical ids have identical distances, so it
        # is safe to keep an arbitrary single occurrence.  Sort ids, flag
        # repeats, scatter flags back to original positions.
        sort_idx = jnp.argsort(pool, axis=1)
        pool_sorted = jnp.take_along_axis(pool, sort_idx, axis=1)
        dup_sorted = jnp.concatenate(
            [jnp.zeros((pool.shape[0], 1), bool), pool_sorted[:, 1:] == pool_sorted[:, :-1]], 1
        )
        dup = jnp.zeros_like(dup_sorted).at[
            jnp.arange(pool.shape[0])[:, None], sort_idx
        ].set(dup_sorted)
        d = jnp.where(dup, jnp.inf, d)
        _, top_idx = jax.lax.top_k(-d, r)
        new_cand = jnp.take_along_axis(pool, top_idx, axis=1)
        new_d = jnp.take_along_axis(d, top_idx, axis=1)
        new_cand = jnp.where(jnp.isinf(new_d), sentinel, new_cand)
        return new_cand.astype(jnp.int32)

    return _blocked(block, cand, bs, r)


@functools.partial(jax.jit, static_argnames=("m", "alpha", "metric", "bs"))
def _robust_prune(xp: jax.Array, cand: jax.Array, m: int, alpha: float, metric: str,
                  bs: int) -> jax.Array:
    """Vectorized occlusion pruning (HNSW `select_neighbors_heuristic`) over
    the sentinel-padded table ``xp`` (N + 1, d), in blocks of ``bs`` rows.

    Keep candidate c_i (ascending by distance) iff for every already-kept
    c_j: alpha * d(c_i, c_j) >= d(node, c_i).
    """
    n, r = cand.shape
    sentinel = n

    def block(node_ids, cand_blk):
        vecs = xp[jnp.clip(cand_blk, 0, n)]  # (b, r, d)
        q = xp[node_ids]
        if metric == "l2":
            diff = vecs - q[:, None, :]
            d_node = jnp.sum(diff * diff, -1)
            cc = vecs[:, :, None, :] - vecs[:, None, :, :]
            d_cc = jnp.sum(cc * cc, -1)  # (b, r, r)
        else:
            d_node = -jnp.einsum("brd,bd->br", vecs, q)
            d_cc = -jnp.einsum("brd,bsd->brs", vecs, vecs)
        invalid = cand_blk >= n
        d_node = jnp.where(invalid, jnp.inf, d_node)
        order = jnp.argsort(d_node, axis=1)
        inv_d = jnp.take_along_axis(d_node, order, 1)
        inv_c = jnp.take_along_axis(cand_blk, order, 1)
        d_cc_o = jnp.take_along_axis(
            jnp.take_along_axis(d_cc, order[:, :, None], 1), order[:, None, :], 2
        )

        def prune_one(dists, d_pair):
            def body(i, kept):
                occluded = jnp.any(kept & (alpha * d_pair[i] < dists[i]) & (jnp.arange(r) < i))
                keep_i = jnp.isfinite(dists[i]) & ~occluded & (jnp.sum(kept) < m)
                return kept.at[i].set(keep_i)

            return jax.lax.fori_loop(0, r, body, jnp.zeros((r,), bool))

        kept = jax.vmap(prune_one)(inv_d, d_cc_o)
        ranked = jnp.where(kept, jnp.arange(r)[None, :], r)
        slot = jnp.argsort(ranked, axis=1)[:, :m]
        out = jnp.take_along_axis(inv_c, slot, 1)
        out_kept = jnp.take_along_axis(kept, slot, 1)
        return jnp.where(out_kept, out, sentinel).astype(jnp.int32)

    return _blocked(block, cand, bs, m)


def _add_reverse_edges(neighbors: np.ndarray, m: int) -> np.ndarray:
    """Host-side reverse-edge augmentation up to out-degree m (vectorized)."""
    n = neighbors.shape[0]
    nb = np.array(neighbors)
    deg = (nb < n).sum(1)
    out = np.full((n, m), n, np.int32)
    # compact existing edges to the left
    rows, cols = np.nonzero(nb < n)
    rank_fwd = np.zeros_like(rows)
    if rows.size:
        # cumcount per row (rows are sorted by construction of nonzero)
        first = np.r_[True, rows[1:] != rows[:-1]]
        idx = np.arange(rows.size)
        start = np.maximum.accumulate(np.where(first, idx, 0))
        rank_fwd = idx - start
    out[rows, rank_fwd] = nb[rows, cols]
    # candidate reverse edges (v <- u), dropping ones already present
    u, v = rows, nb[rows, cols].astype(np.int64)
    key_exist = u.astype(np.int64) * (n + 1) + v
    key_rev = v * (n + 1) + u
    fresh = ~np.isin(key_rev, key_exist, assume_unique=False)
    # dedup duplicate reverse pairs
    key_rev_f = key_rev[fresh]
    uniq, uniq_idx = np.unique(key_rev_f, return_index=True)
    rv = v[fresh][uniq_idx]
    ru = u[fresh][uniq_idx]
    order = np.argsort(rv, kind="stable")
    rv, ru = rv[order], ru[order]
    if rv.size:
        first = np.r_[True, rv[1:] != rv[:-1]]
        idx = np.arange(rv.size)
        start = np.maximum.accumulate(np.where(first, idx, 0))
        rank = idx - start
        slot = deg[rv] + rank
        ok = slot < m
        out[rv[ok], slot[ok]] = ru[ok]
    return out


def _repair_connectivity(neighbors: np.ndarray, x: np.ndarray, entry: int, metric: str) -> np.ndarray:
    """Directed reachability repair: traversal follows out-edges, so repair
    must too.  BFS from the entry; while nodes remain unreached, bridge the
    closest (reached -> unreached) sampled pair bidirectionally and extend
    the BFS from the new node.  Mirrors the connectivity HNSW gets from
    insertion-time search, which a batch build must enforce explicitly."""
    n = neighbors.shape[0]
    out = np.array(neighbors)
    rng = np.random.default_rng(0)
    x2 = (x * x).sum(1)
    # the (1024, r_rows) distance block of a round costs what it does at
    # 128-d: 4096 reached rows up to 128-d, 512 at 960-d
    r_rows = _rows_within(_REPAIR_SAMPLE, x.shape[1])

    reached = np.zeros(n, bool)

    def bfs_from(seeds):
        frontier = np.asarray(seeds, np.int64)
        reached[frontier] = True
        while frontier.size:
            nxt = out[frontier].reshape(-1)
            nxt = nxt[nxt < n]
            nxt = np.unique(nxt)
            nxt = nxt[~reached[nxt]]
            reached[nxt] = True
            frontier = nxt

    bfs_from([entry])
    for _ in range(n):  # each round strictly shrinks the unreached set
        unreached = np.where(~reached)[0]
        if unreached.size == 0:
            break
        r_nodes = np.where(reached)[0]
        r_sample = r_nodes[rng.integers(0, r_nodes.size, min(r_rows, r_nodes.size))]
        u_sample = unreached[rng.integers(0, unreached.size, min(1024, unreached.size))]
        if metric == "l2":
            dmat = (
                x2[u_sample][:, None]
                + x2[r_sample][None, :]
                - 2.0 * (x[u_sample] @ x[r_sample].T)
            )
        else:
            dmat = -(x[u_sample] @ x[r_sample].T)
        i, j = np.unravel_index(np.argmin(dmat), dmat.shape)
        u, v = int(u_sample[i]), int(r_sample[j])  # u unreached, v reached
        for a, b in ((v, u), (u, v)):
            slots = np.where(out[a] >= n)[0]
            out[a, slots[0] if len(slots) else -1] = b
        bfs_from([u])
    return out


# ---------------------------------------------------------------------------
# Local maintenance (mutable-index compaction, core/mutable/compact.py):
# batch node removal + batch local insertion.  HNSW gets incremental
# maintenance from insertion-time search; a batch-built flat graph gets it
# from these two host-side primitives plus the same connectivity repair the
# initial build runs.
# ---------------------------------------------------------------------------


def remove_nodes(neighbors: np.ndarray, keep: np.ndarray) -> np.ndarray:
    """Drop the nodes where ``keep`` is False and reindex the survivors.

    neighbors: (N, M) int32 with sentinel == N.  Returns (N_keep, M) with
    sentinel == N_keep; edges into removed nodes are dropped and each row's
    surviving edges are compacted to the left (the iterators treat the
    first sentinel as end-of-row only implicitly, but compaction keeps the
    rows dense for the insertion step's reverse-edge scan).
    """
    n, m = neighbors.shape
    keep = np.asarray(keep, bool)
    kept_pos = np.where(keep)[0]
    n_keep = kept_pos.size
    new_id = np.full((n + 1,), n_keep, np.int64)  # removed & sentinel -> sentinel
    new_id[kept_pos] = np.arange(n_keep)
    nb = neighbors[kept_pos].astype(np.int64)
    mapped = new_id[np.clip(nb, 0, n)]
    out = np.full((n_keep, m), n_keep, np.int32)
    rows, cols = np.nonzero(mapped < n_keep)
    if rows.size:
        first = np.r_[True, rows[1:] != rows[:-1]]
        idx = np.arange(rows.size)
        start = np.maximum.accumulate(np.where(first, idx, 0))
        out[rows, idx - start] = mapped[rows, cols]
    return out


def _occlusion_prune_host(d_node: np.ndarray, cand: np.ndarray, x: np.ndarray, m: int, alpha: float, metric: str) -> np.ndarray:
    """Greedy occlusion prune of one candidate list (ascending by d_node);
    host-side counterpart of `_robust_prune` for small insertion batches."""
    order = np.argsort(d_node, kind="stable")
    kept: list[int] = []
    for j in order:
        if len(kept) >= m or not np.isfinite(d_node[j]):
            break
        c = x[cand[j]]
        if kept:
            kx = x[cand[kept]]
            if metric == "l2":
                d_ck = ((kx - c) ** 2).sum(1)
            else:
                d_ck = -(kx @ c)
            if np.any(alpha * d_ck < d_node[j]):
                continue
        kept.append(int(j))
    return cand[kept]


def insert_nodes(
    neighbors: np.ndarray,
    x: np.ndarray,
    n_old: int,
    assign: np.ndarray,
    centroids: np.ndarray,
    m: int,
    *,
    alpha: float = 1.2,
    link: int = 4,
    metric: str = "l2",
) -> np.ndarray:
    """Insert nodes ``n_old..n-1`` of ``x`` into an existing graph.

    neighbors: (n_old, M) with sentinel == n_old.  Returns (n, M) with
    sentinel == n.  Mirrors HNSW insertion locally: each new node draws its
    candidate pool from the ``link`` clusters nearest its own (by centroid
    distance), keeps an occlusion-pruned top-``m``, and pushes reverse
    edges, evicting the farthest edge of a full row.  Connectivity repair
    (and entry choice) is the caller's job — compaction runs
    ``_repair_connectivity`` once over the folded graph.
    """
    x = np.asarray(x, np.float32)
    n = x.shape[0]
    new_ids = np.arange(n_old, n)
    out = np.full((n, m), n, np.int32)
    old = neighbors.astype(np.int64)
    out[:n_old] = np.where(old >= n_old, n, old).astype(np.int32)
    if new_ids.size == 0:
        return out
    kc = centroids.shape[0]
    link = min(link, kc)
    cdist = np.asarray(pairwise(jnp.asarray(centroids), jnp.asarray(centroids), metric))
    near_clusters = np.argsort(cdist, axis=1)[:, :link]  # (kc, link)
    members = [np.where(assign == c)[0] for c in range(kc)]
    x2 = (x * x).sum(1)
    deg = (out < n).sum(1)
    for i in new_ids:
        pool = np.concatenate([members[cc] for cc in near_clusters[assign[i]]])
        pool = pool[pool != i]
        if pool.size == 0:  # degenerate corpus: leave isolated, repair bridges
            continue
        xy = x[pool] @ x[i]
        d = x2[pool] - 2.0 * xy + x2[i] if metric == "l2" else -xy
        chosen = _occlusion_prune_host(d, pool, x, m, alpha, metric)
        out[i, : chosen.size] = chosen
        deg[i] = chosen.size
        # reverse edges: append while the row has room, else evict the
        # farthest edge if the new one is closer (plain distance eviction;
        # occlusion re-pruning on every reverse edge is not worth the host
        # cost at delta scale)
        for j in chosen:
            if deg[j] < m:
                out[j, deg[j]] = i
                deg[j] += 1
                continue
            row = out[j]
            rv = x[row] - x[j]
            d_row = (rv * rv).sum(1) if metric == "l2" else -(x[row] @ x[j])
            w = int(np.argmax(d_row))
            d_new = (
                float(((x[i] - x[j]) ** 2).sum()) if metric == "l2" else float(-(x[i] @ x[j]))
            )
            if d_new < d_row[w]:
                out[j, w] = i
    return out


def build_graph(
    vectors: np.ndarray,
    m: int = 16,
    *,
    n_candidates: int | None = None,
    n_build_clusters: int | None = None,
    link: int = 4,
    nn_descent_rounds: int = 1,
    prune_alpha: float = 1.2,
    metric: str = "l2",
    seed: int = 0,
) -> GraphIndex:
    """Build a flat navigable proximity graph with max out-degree ``m``."""
    x = np.asarray(vectors, np.float32)
    n, d = x.shape
    n_candidates = n_candidates or max(2 * m, 16)
    n_build_clusters = n_build_clusters or max(8, min(n // 128, 4096))
    phase = lambda name: timed("index_build_phase", phase=name, n_rows=n)
    bs = _block_rows(d)
    xp_s = jax.ShapeDtypeStruct((n + 1, d), jnp.float32)
    cand_s = jax.ShapeDtypeStruct((n, n_candidates), jnp.int32)
    with ThreadPoolExecutor(1) as ex:
        # the device rounds compile on a worker thread beside the k-means
        # and the host pools: the programs the plain calls would compile
        nn_round = ex.submit(lambda: _nn_descent_round.lower(xp_s, cand_s, metric, bs).compile())
        prune = ex.submit(
            lambda: _robust_prune.lower(xp_s, cand_s, m, prune_alpha, metric, bs).compile())
        with phase("graph_kmeans"):
            km = kmeans(jnp.asarray(x), n_build_clusters, iters=8, seed=seed, metric=metric)
            assign = np.asarray(km.assignments)
        with phase("graph_pools"):
            cand = _topk_neighbors_in_pools(
                x, assign, np.asarray(km.centroids), n_candidates, link, metric
            )
        nn_round, prune = nn_round.result(), prune.result()
    # the one device copy of the table during the rounds: sentinel-padded
    xp = jnp.asarray(np.concatenate([x, np.zeros((1, d), np.float32)], 0))
    cand_j = jnp.asarray(cand)
    with phase("graph_nn_descent"):
        for _ in range(nn_descent_rounds):
            cand_j = nn_round(xp, cand_j)
        cand_j.block_until_ready()
    with phase("graph_prune"):
        pruned = np.asarray(prune(xp, cand_j))
    del xp, cand_j
    with phase("graph_reverse_edges"):
        neighbors = _add_reverse_edges(pruned, m)
    with phase("graph_repair"):
        # medoid entry: point nearest to the global mean
        mean = x.mean(0, keepdims=True)
        entry = int(np.argmin(np.asarray(pairwise(jnp.asarray(mean), jnp.asarray(x),
                                                  metric))[0]))
        neighbors = _repair_connectivity(neighbors, x, entry, metric)
    return GraphIndex(jnp.asarray(neighbors), jnp.asarray(np.int32(entry)))
