"""Clustered relational indices — the TPU-native equivalent of the paper's
"clustered B+-trees" (§IV.A).

Hardware adaptation (recorded in DESIGN.md): a B+-tree is a pointer-chasing
structure with no TPU analogue.  Its role in Compass is exactly two
operations per (cluster, attribute): (1) locate the contiguous run of
records whose attribute value falls in a query range, (2) iterate that run.
A *cluster-major sorted permutation* + fixed-depth binary search provides
identical O(log n + m) semantics with pure array reads:

  order[a]       : (N,)  int32 — record ids sorted by (cluster, attr_a)
  sorted_vals[a] : (N,)  f32   — attr_a values in that order
  offsets        : (nlist+1,) int32 — CSR cluster boundaries

A range probe inside cluster ``c`` is a branchless binary search confined
to ``[offsets[c], offsets[c+1])`` — the "B+-tree descent" — and the run
``order[a][beg:end]`` is the leaf scan.  The engine's probe
(:func:`run_position`) reads one element ``sorted_vals[a, mid]`` of the 2-D
runs per halving, ``N.bit_length()`` halvings for N records: a range of at
most N rows is empty after that many.  Updates to attribute values are
per-cluster re-sorts (cheap, local), mirroring the paper's point that only
the relational side needs maintenance on attribute update.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class ClusteredAttrs(NamedTuple):
    order: jax.Array  # (A, N) int32: record ids, cluster-major, attr-sorted
    sorted_vals: jax.Array  # (A, N) f32: values aligned with `order`
    offsets: jax.Array  # (nlist + 1,) int32
    assignments: jax.Array  # (N,) int32 cluster of each record

    @property
    def n_attrs(self) -> int:
        return self.order.shape[0]

    @property
    def n_records(self) -> int:
        return self.order.shape[1]

    @property
    def n_clusters(self) -> int:
        return self.offsets.shape[0] - 1


def build_clustered_attrs(attrs: np.ndarray, assignments: np.ndarray, nlist: int) -> ClusteredAttrs:
    """Host-side build: sort each attribute within each cluster."""
    attrs = np.asarray(attrs, np.float32)
    assignments = np.asarray(assignments, np.int64)
    n, n_attrs = attrs.shape
    counts = np.bincount(assignments, minlength=nlist)
    offsets = np.zeros(nlist + 1, np.int32)
    np.cumsum(counts, out=offsets[1:])
    order = np.empty((n_attrs, n), np.int32)
    sorted_vals = np.empty((n_attrs, n), np.float32)
    for a in range(n_attrs):
        # lexsort: primary key cluster, secondary key attribute value.
        perm = np.lexsort((attrs[:, a], assignments))
        order[a] = perm.astype(np.int32)
        sorted_vals[a] = attrs[perm, a]
    return ClusteredAttrs(
        jnp.asarray(order),
        jnp.asarray(sorted_vals),
        jnp.asarray(offsets),
        jnp.asarray(assignments.astype(np.int32)),
    )


_BSEARCH_ITERS = 32  # supports N up to 2^32


def searchsorted_slice(vals: jax.Array, lo_idx, hi_idx, x, side: str = "left"):
    """Insertion point of ``x`` within ``vals[lo_idx:hi_idx]`` (global index).

    Branchless fixed-depth binary search over one attribute's run ``vals``;
    all arguments may be traced.  The planner's probe
    (:func:`run_bounds_all_clusters`); the engine's is :func:`run_position`.
    """

    def body(_, bounds):
        lo, hi = bounds
        valid = lo < hi
        mid = (lo + hi) // 2
        v = vals[jnp.clip(mid, 0, vals.shape[0] - 1)]
        go_right = (v < x) if side == "left" else (v <= x)
        new_lo = jnp.where(go_right, mid + 1, lo)
        new_hi = jnp.where(go_right, hi, mid)
        return (jnp.where(valid, new_lo, lo), jnp.where(valid, new_hi, hi))

    lo, hi = jax.lax.fori_loop(0, _BSEARCH_ITERS, body, (lo_idx, hi_idx))
    return lo


def run_position(vals: jax.Array, attr, lo_idx, hi_idx, x, right=False):
    """Insertion point of ``x`` within ``vals[attr, lo_idx:hi_idx]`` (global
    index): the first position whose value is ``>= x`` (``right`` false) or
    ``> x`` (``right`` true).

    ``vals`` is the ``(A, N)`` runs; every other argument may be traced and
    they broadcast together, so one call runs many searches side by side.
    Each halving reads single elements ``vals[attr, mid]`` — under ``vmap``
    a scalar gather from the unbatched runs, never a per-lane copy of a
    run — and ``N.bit_length()`` halvings empty any range of at most N rows.
    """
    n = vals.shape[1]
    attr, lo, hi, x, right = jnp.broadcast_arrays(attr, lo_idx, hi_idx, x, right)

    def body(_, bounds):
        lo, hi = bounds
        valid = lo < hi
        mid = (lo + hi) // 2
        v = vals[attr, jnp.clip(mid, 0, n - 1)]
        go_right = jnp.where(right, v <= x, v < x)
        new_lo = jnp.where(go_right, mid + 1, lo)
        new_hi = jnp.where(go_right, hi, mid)
        return (jnp.where(valid, new_lo, lo), jnp.where(valid, new_hi, hi))

    lo, _ = jax.lax.fori_loop(0, n.bit_length(), body, (lo, hi))
    return lo


def range_in_cluster(ca: ClusteredAttrs, cluster, attr, lo_val, hi_val):
    """(beg, end) global positions into ``order[attr]`` for records of
    ``cluster`` with attr value in the closed interval [lo_val, hi_val]."""
    c_beg = ca.offsets[cluster]
    c_end = ca.offsets[cluster + 1]
    beg = run_position(ca.sorted_vals, attr, c_beg, c_end, lo_val)
    end = run_position(ca.sorted_vals, attr, c_beg, c_end, hi_val, right=True)
    return beg, end


def count_in_cluster(ca: ClusteredAttrs, cluster, attr, lo_val, hi_val):
    beg, end = range_in_cluster(ca, cluster, attr, lo_val, hi_val)
    return end - beg


def run_bounds_all_clusters(ca: ClusteredAttrs, attr, lo_val, hi_val):
    """Per-cluster [beg, end) run bounds over ``order[attr]`` for records
    whose attr value lies in the closed interval [lo_val, hi_val] — every
    cluster probed at once (vmapped B+-tree descents).

    This is the planner's exact pass-count probe: ``sum(end - beg)`` is the
    exact number of records matching the single-attribute range, and the
    bounds themselves are the PREFILTER mode's materialization cursors.
    Returns (beg, end), each (nlist,) int32 global positions.
    """
    vals = ca.sorted_vals[attr]
    c_beg = ca.offsets[:-1]
    c_end = ca.offsets[1:]
    beg = jax.vmap(lambda b, e: searchsorted_slice(vals, b, e, lo_val, "left"))(c_beg, c_end)
    end = jax.vmap(lambda b, e: searchsorted_slice(vals, b, e, hi_val, "right"))(c_beg, c_end)
    return beg, end
