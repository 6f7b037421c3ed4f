"""Delta segment: the mutable tier of the LSM-style index (core/mutable).

Recent upserts live in a fixed-capacity segment with their own vectors and
attribute rows, padded to a static shape (``cap`` slots + one sentinel row)
so the search path stays fully jitted whatever the fill level.  Search over
the delta is a brute-force predicate-filtered scan — at delta scale
(hundreds to a few thousand rows) one fused gather+distance+predicate pass
is cheaper than maintaining any structure, and it is *exact*, so the delta
never costs recall.  The scan reuses the engine's batched
``VisitBackend.scan_scores`` surface (``kernels/filter_distance``'s (B, V)
grid on the pallas path), exactly like the planner's PREFILTER mode.

Slots are append-only between compactions: a re-upsert of a delta-resident
id invalidates the old slot rather than rewriting it, so a snapshot taken
earlier stays internally consistent (epoch swap, see mutable_index.py).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.obs.profiling import staged

from ..quant.encode import QuantizedVectors


class DeltaView(NamedTuple):
    """Device-side snapshot of the delta segment (a JAX pytree).

    Mirrors just enough of :class:`~repro.core.index.CompassIndex`'s row
    layout (sentinel-padded ``vectors``/``attrs``, ``n_records``, optional
    ``qvecs``) that the engine's ``VisitBackend.scan_scores`` /
    ``scan_scores_quantized`` accept it unchanged.
    """

    vectors: jax.Array  # (cap + 1, d) — sentinel row cap is zeros
    attrs: jax.Array  # (cap + 1, A) — sentinel row is +inf (fails ranges)
    gids: jax.Array  # (cap,) int32 global record ids; -1 on empty slots
    valid: jax.Array  # (cap,) bool — occupied and not superseded/deleted
    # delta rows encoded against the *base's frozen codebooks* (attached by
    # MutableIndex.snapshot when the base carries a quantized tier), so the
    # quantized scan is one ADC pass over base+delta with shared tables
    qvecs: Optional[QuantizedVectors] = None

    @property
    def n_records(self) -> int:
        return self.vectors.shape[0] - 1

    @property
    def cap(self) -> int:
        return self.gids.shape[0]


@staged("mutable/delta")
def delta_topk(delta: DeltaView, queries, pred, k: int, metric: str, backend):
    """Exact top-k over the delta segment for a query batch.

    Returns (gids (B, k') int32 with -1 padding, dists (B, k') f32 with
    +inf padding, n_scanned () int32, n_pass (B,) int32 predicate-passing
    rows per query) where k' = min(k, cap).
    """
    b = queries.shape[0]
    cap = delta.cap
    ids = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32), (b, cap))
    mask = jnp.broadcast_to(delta.valid, (b, cap))
    dist, passing = backend.scan_scores(delta, queries, pred, ids, mask, metric)
    dist = jnp.where(passing, dist, jnp.inf)
    kk = min(k, cap)
    neg, sel = jax.lax.top_k(-dist, kk)
    top_d = -neg
    top_g = jnp.where(jnp.isfinite(top_d), jnp.take(delta.gids, sel), jnp.int32(-1))
    n_pass = jnp.sum(passing, axis=1).astype(jnp.int32)
    return top_g, top_d, jnp.sum(delta.valid).astype(jnp.int32), n_pass


@staged("mutable/delta")
def delta_topk_quantized(
    delta: DeltaView, queries, pred, k: int, metric: str, backend, quant,
    luts=None,
):
    """Quantized two-stage top-k over the delta segment.

    Stage one is the same brute scan as :func:`delta_topk` but over the PQ
    codes (``VisitBackend.scan_scores_quantized`` — the pq_score kernel's
    (B, cap) grid on the pallas path, exactly like the planner's PREFILTER
    materialization), widened to ``k * refine_factor`` survivors; stage two
    re-scores those exactly per ``quant.rerank`` ("full": the float32 delta
    rows, "decode": decoded codes, "none": trust the ADC order).

    ``luts`` optionally supplies the per-query ADC tables —
    the delta's codebooks are the base's frozen codebooks (see
    DeltaView.qvecs), so ``mutable_search`` builds the tables once and
    shares them with the base search; built here when omitted.

    Returns (gids (B, k') int32 with -1 padding, dists (B, k') f32 with
    +inf padding, n_adc (B,) int32 stage-one table scores, n_rerank (B,)
    int32 stage-two exact distances, n_pass (B,) int32 predicate-passing
    rows per query) with k' = min(k, cap).
    """
    from ..quant import encode as Q
    from ..quant.rerank import rerank_candidates

    b = queries.shape[0]
    cap = delta.cap
    ids = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32), (b, cap))
    mask = jnp.broadcast_to(delta.valid, (b, cap))
    if luts is None:
        luts = Q.build_luts(delta.qvecs, queries, metric)
    dist, passing = backend.scan_scores_quantized(delta, luts, pred, ids, mask, metric)
    dist = jnp.where(passing, dist, jnp.inf)
    n_adc = jnp.sum(mask, axis=1).astype(jnp.int32)
    k1 = min(k * quant.refine_factor, cap)
    neg1, sel1 = jax.lax.top_k(-dist, k1)  # stage-one ADC survivors
    cand_mask = jnp.isfinite(-neg1)
    # stage two is the same rerank step the base tier runs (quant/rerank.py)
    sel2, top_d, n_rerank = rerank_candidates(
        delta, queries, pred, sel1, -neg1, cand_mask, k, metric, backend, quant.rerank
    )
    slots = jnp.take_along_axis(sel1, sel2, axis=1)
    top_g = jnp.where(jnp.isfinite(top_d), jnp.take(delta.gids, slots), jnp.int32(-1))
    n_pass = jnp.sum(passing, axis=1).astype(jnp.int32)
    return top_g, top_d, n_adc, n_rerank, n_pass
