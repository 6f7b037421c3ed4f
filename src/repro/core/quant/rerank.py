"""Stage two of quantized search: exact rerank of the ADC survivors.

Stage one runs the ordinary engine loop with ADC scoring at a widened
``ef * refine_factor`` result queue; this module re-scores those survivors
and keeps the top ``k``.  Three scorers (``QuantParams.rerank``):

  * ``"full"``   — fused gather+distance+predicate over the full-precision
    rows (``VisitBackend.scan_scores``, i.e. the ``filter_distance`` kernel
    on the pallas path): the default, and what makes quantized top-k match
    exact search once ``refine_factor`` covers the ADC ordering error.
  * ``"decode"`` — distances against decoded codes, for indices that
    dropped the float32 table.  The l2 ADC table already sums to the exact
    decoded distance, so this only canonicalizes summation order — recall
    is bounded by quantization error, which is the honest trade.
  * ``"none"``   — trust ADC ordering, truncate to ``k``.

The stable-id / padding contract is preserved: empty slots keep ``+inf``
distance and the sentinel id ``n_records``, exactly as in exact search.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs.profiling import staged

from .encode import decode


def decode_distances(qv, queries, ids, mask, metric: str) -> jax.Array:
    """(B, E) distances between queries and decoded candidate rows."""
    vecs = decode(qv, jnp.clip(ids, 0, qv.n_records))  # (B, E, d)
    if metric == "l2":
        diff = vecs - queries[:, None, :]
        dist = jnp.sum(diff * diff, axis=-1)
    else:
        dist = -jnp.einsum("bed,bd->be", vecs, queries)
    return jnp.where(mask, dist, jnp.inf)


def rerank_candidates(view, queries, pred, ids, dists1, mask, k, metric, backend, mode):
    """The shared stage-two step: re-score survivors, take the top ``k``.

    ``view`` is any index-like pytree the backend scan surfaces accept
    (``CompassIndex`` or the mutable tier's ``DeltaView`` — both carry
    sentinel-padded ``vectors``/``attrs`` and ``qvecs``); ``ids``/
    ``dists1``/``mask`` are the (B, E) stage-one survivors in ADC order.
    Returns ``(sel (B, k') int32 positions into E, dists (B, k') f32 with
    +inf padding, n_rerank (B,) int32 exact distances computed)``,
    k' = min(k, E).  Used by both :func:`rerank_batch` (base tier) and
    ``mutable.delta.delta_topk_quantized`` so the two tiers cannot drift.
    """
    kk = min(k, ids.shape[1])
    if mode == "none":
        # trust ADC order: top-k over the stage-one distances (already
        # sorted for the base result queue; cheap either way), zero exact
        # distances computed
        ex_d = jnp.where(mask, dists1, jnp.inf)
        n_rerank = jnp.zeros((ids.shape[0],), jnp.int32)
    elif mode == "full":
        ex_d, passing = backend.scan_scores(view, queries, pred, ids, mask, metric)
        ex_d = jnp.where(passing, ex_d, jnp.inf)
        n_rerank = jnp.sum(mask, axis=1).astype(jnp.int32)
    else:  # "decode"
        ex_d = decode_distances(view.qvecs, queries, ids, mask, metric)
        n_rerank = jnp.sum(mask, axis=1).astype(jnp.int32)
    neg, sel = jax.lax.top_k(-ex_d, kk)
    return sel, -neg, n_rerank


@staged("quant/rerank")
def rerank_batch(index, queries, pred, res, k: int, metric: str, backend, mode: str):
    """Exact rerank of a stage-one SearchResult -> top-``k`` SearchResult.

    ``res.ids``/``res.dists`` are the (B, E) ADC-ordered survivors
    (E == stage-one ef).  Returns the same NamedTuple type with stats
    updated: ``n_rerank`` counts stage-two distance evaluations, and
    ``n_dist`` additionally counts them when they read full-precision rows
    (mode ``"full"``) — ``n_dist`` stays the full-precision #Comp figure.
    """
    n = index.n_records
    ids, dists = res.ids, res.dists
    mask = jnp.isfinite(dists)  # (B, E) live result-queue entries
    sel, out_d, n_rerank = rerank_candidates(
        index, queries, pred, ids, dists, mask, k, metric, backend, mode
    )
    out_i = jnp.where(
        jnp.isfinite(out_d), jnp.take_along_axis(ids, sel, axis=1), jnp.int32(n)
    )
    stats = res.stats._replace(n_rerank=res.stats.n_rerank + n_rerank)
    if mode == "full":
        stats = stats._replace(n_dist=stats.n_dist + n_rerank)
    return res._replace(ids=out_i, dists=out_d, stats=stats)
