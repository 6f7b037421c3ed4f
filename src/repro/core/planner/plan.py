"""Cost-based per-query execution-mode selection (DESIGN.md §Planner).

Compass's robustness claim is that cooperative G.NEXT/B.NEXT execution stays
competitive across selectivity regimes — but at the extremes a specialized
plan is strictly better, and the filtered-ANN literature (JAG, the 2026
survey) puts the prefilter/graph crossover as the single biggest lever.
The planner closes that gap *inside* the jitted batch: per query it picks
one of three modes from attribute statistics, with no host round-trip.

  * ``PREFILTER``   — the exact chosen-attr runs are small enough
    (``run_total <= prefilter_cap``, i.e. estimated matches ≲ O(ef)) that
    materializing them and running one fused ``filter_distance`` top-k scan
    is cheaper than any graph walk — and exact: every record passing a DNF
    term appears in that term's chosen-attr run, so scanning all runs is a
    brute-force filtered scan over a superset of the matches.
  * ``COOPERATIVE`` — the paper's Algorithm 1 loop (the robust default).
  * ``POSTFILTER``  — selectivity ≈ 1: the filter is nearly vacuous, the
    relational iterator can only inject attribute-ordered (distance-random)
    candidates, so run graph-dominant (B.NEXT disabled).

Mode dispatch is traceable: the driver branches on the (traced) mode with
``lax.cond``; under ``vmap`` both branches execute masked, which is exactly
the TPU-correct trade — the PREFILTER scan is a bounded ``prefilter_cap``-row
kernel and an all-COOPERATIVE batch skips the scan entirely through the
batch-level ``lax.cond`` in :func:`plan_batch` (a *scalar* predicate, so it
stays a real branch after jit).

Cost model: single-dimensional "row units" (one fused scan row ≈ 1).  The
constants below were calibrated on the bench_planner sweep (CPU interpret
path; see DESIGN.md §Planner for the recalibration recipe — rerun the sweep,
fit per-query wall clock against ``run_total`` / ``ef``).
"""
from __future__ import annotations

from typing import TYPE_CHECKING, NamedTuple

import jax
import jax.numpy as jnp

from repro.obs.profiling import staged

from .. import predicate as P
from ..engine.state import dedup_new
from . import estimate as E
from .stats import term_run_bounds

if TYPE_CHECKING:  # runtime import would cycle: index builds planner stats
    from ..index import CompassIndex

# Execution modes (stats.mode values; order matters: argmin over the cost
# vector [prefilter, cooperative, postfilter] yields the mode id).
PREFILTER, COOPERATIVE, POSTFILTER = 0, 1, 2
MODE_NAMES = ("prefilter", "cooperative", "postfilter")

# -- calibrated cost-model constants (row units) ----------------------------
# The binary-search probes themselves are deliberately NOT charged to any
# arm: they run in plan_query before mode selection, for every mode alike,
# so they are a sunk cost that must not bias the decision.
COST_PRE_ROW = 1.0  # score one materialized run row (fused gather+dist+pred)
COST_COOP_EF = 8.0  # per result-slot cost of the cooperative loop: queue
#   sorts + beam visits dominate and are ~flat in selectivity (the paper's
#   robustness result), so cost ≈ COST_COOP_EF * ef.
COST_POST_ROW = 1.5  # per-visit cost of the graph-only loop; the loop must
#   oversample by 1/selectivity to fill ef passing results.  The fused
#   visit_step kernel (engine/5) cheapens a visited row on the compiled
#   path, but it cheapens COOPERATIVE and POSTFILTER visits identically —
#   both modes score through the same backend.visit_step — so the
#   *relative* constants here are unchanged; bench_kernels' visit_step
#   rows are the tracking artifact for the absolute per-row cost.
SEL_FLOOR = 1e-4  # avoid division blow-up on est_sel ~ 0
# -- quantized-tier costs (CompassParams.quant active) ----------------------
# ADC scores a row with m table lookups instead of a d-dim gather+reduce:
# bytes moved drop from 4*d to m per row, so a scanned row is ~4x cheaper.
# Calibration source: bench_quant's scan microbench (adc_scan vs exact_scan
# wall per row).  Last measured at n=20000, d=48, m ∈ {4, 8, 16}:
# cost_adc/cost_exact = 0.24 / 0.31 / 0.19 — flat in m because the
# (V, m) LUT gathers, not the arithmetic, dominate the scan on this path.
COST_ADC_ROW = 0.25
COST_RERANK_ROW = 1.0


class QueryPlan(NamedTuple):
    """Per-query plan: chosen mode + the PREFILTER materialization."""

    mode: jax.Array  # () int32: PREFILTER | COOPERATIVE | POSTFILTER
    est_sel: jax.Array  # () f32 estimated DNF selectivity
    run_total: jax.Array  # () int32 exact total chosen-attr run size
    ids: jax.Array  # (prefilter_cap,) int32 materialized candidate ids
    mask: jax.Array  # (prefilter_cap,) bool valid (deduped) slots


class PlannedBatch(NamedTuple):
    """Batch of plans + pre-scored PREFILTER candidates (driver input)."""

    mode: jax.Array  # (B,) int32
    est_sel: jax.Array  # (B,) f32
    run_total: jax.Array  # (B,) int32
    ids: jax.Array  # (B, cap) int32
    mask: jax.Array  # (B, cap) bool — valid & mode == PREFILTER
    dist: jax.Array  # (B, cap) f32, +inf where masked
    passing: jax.Array  # (B, cap) bool full-DNF pass


def plan_query(index: CompassIndex, pred_lo, pred_hi, pm, quant: bool = False) -> QueryPlan:
    """Plan one query (traceable; vmapped over the batch by plan_batch).

    pred_lo / pred_hi: (T, A) DNF interval tensors.  ``pm`` must be
    resolved (``prefilter_cap`` > 0).  With ``quant`` (static) the cost
    model prices scanned/visited rows at the ADC rate and adds each arm's
    exact-rerank bill; ``pm.ef`` is then already the widened stage-one
    queue (ef * refine_factor — the driver rewrites it before planning).
    """
    ca = index.cattrs
    nlist = index.nlist
    cap = pm.prefilter_cap
    T = pred_lo.shape[0]
    chosen = P.chosen_attrs(P.Predicate(pred_lo, pred_hi))

    # exact probes (these double as the materialization cursors)
    beg, end = term_run_bounds(ca, pred_lo, pred_hi, chosen)  # (T, nlist)
    rem = jnp.maximum(end - beg, 0)
    run_total = jnp.sum(rem).astype(jnp.int32)

    # histogram estimate (cluster-refined)
    _, est_sel = E.estimate_matches(index.astats, pred_lo, pred_hi)

    # cost model -> mode
    if quant:
        # ADC rows are cheap; the exact rerank of the survivors is not.
        # PREFILTER's queue holds at most its run_total matches, the loop
        # modes rerank the full widened queue (ef here == ef * refine).
        rerank_pre = COST_RERANK_ROW * jnp.minimum(run_total, pm.ef)
        rerank_loop = jnp.float32(COST_RERANK_ROW * pm.ef)
        cost_pre = jnp.where(
            run_total <= cap, COST_ADC_ROW * run_total + rerank_pre, jnp.inf
        )
        cost_coop = jnp.float32(COST_COOP_EF * pm.ef) + rerank_loop
        post_row = COST_POST_ROW * COST_ADC_ROW / COST_PRE_ROW
    else:
        rerank_loop = jnp.float32(0.0)
        cost_pre = jnp.where(run_total <= cap, COST_PRE_ROW * run_total, jnp.inf)
        cost_coop = jnp.float32(COST_COOP_EF * pm.ef)
        post_row = COST_POST_ROW
    if pm.use_graph:
        cost_post = jnp.where(
            est_sel >= pm.postfilter_min_sel,
            post_row * pm.ef / jnp.maximum(est_sel, SEL_FLOOR) + rerank_loop,
            jnp.inf,
        )
    else:  # CompassRelational ablation: no graph to run POSTFILTER on
        cost_post = jnp.float32(jnp.inf)
    mode = jnp.argmin(jnp.stack([cost_pre, cost_coop, cost_post])).astype(jnp.int32)

    # materialize up to `cap` run positions, term-major then cluster-major
    # (same slot->segment mapping as B.NEXT's fetch, over all T*nlist runs)
    flat_beg = beg.reshape(-1)
    flat_rem = rem.reshape(-1)
    cum = jnp.cumsum(flat_rem)
    total = cum[-1]
    slots = jnp.arange(cap, dtype=jnp.int32)
    seg = jnp.clip(
        jnp.searchsorted(cum, slots, side="right").astype(jnp.int32), 0, T * nlist - 1
    )
    before = jnp.where(seg > 0, cum[jnp.maximum(seg - 1, 0)], 0)
    pos = flat_beg[seg] + (slots - before)
    ok = slots < jnp.minimum(total, cap)
    attr_of = chosen[seg // nlist]
    ids = ca.order[attr_of, jnp.clip(pos, 0, ca.n_records - 1)]
    # a record can sit in several terms' runs (disjunctions) — same
    # duplicate-drop the engine applies to visit lists
    mask = dedup_new(ids, ok)
    return QueryPlan(mode, est_sel, run_total, ids, mask)


@staged("planner")
def plan_batch(
    index: CompassIndex, queries, pred: P.Predicate, pm, backend, luts=None
) -> PlannedBatch:
    """Plan every query in the batch and pre-score the PREFILTER candidates.

    The candidate scan is hoisted out of the per-query vmap (like the
    centroid ranking) so the pallas backend sees one blocked (B, cap)
    ``filter_distance`` problem, and it is guarded by a *batch-level*
    ``lax.cond`` on "any query chose PREFILTER" — a scalar predicate, so an
    all-COOPERATIVE batch pays only the probes, not the scan.

    With ``luts`` (the quantized tier: per-query (m, ks) ADC tables, built
    by the driver), the scan runs
    over the PQ codes instead (``scan_scores_quantized`` — the pq_score
    kernel's (B, cap) grid) and the cost model prices rows at the ADC rate;
    the materialized candidates then carry ADC distances, which stage two's
    exact rerank re-scores like every other quantized result.
    """
    if index.astats is None:
        raise ValueError(
            "CompassParams(planner=True) requires index attribute statistics; "
            "rebuild the index with build_index (build_attr_stats) first"
        )
    quant = luts is not None
    plans = jax.vmap(lambda lo, hi: plan_query(index, lo, hi, pm, quant))(
        pred.lo, pred.hi
    )
    scan_mask = plans.mask & (plans.mode == PREFILTER)[:, None]
    b, cap = scan_mask.shape

    def do_scan(_):
        if quant:
            dist, passing = backend.scan_scores_quantized(
                index, luts, pred, plans.ids, scan_mask, pm.metric
            )
        else:
            dist, passing = backend.scan_scores(
                index, queries, pred, plans.ids, scan_mask, pm.metric
            )
        return dist, passing & scan_mask

    def no_scan(_):
        return (
            jnp.full((b, cap), jnp.inf, jnp.float32),
            jnp.zeros((b, cap), bool),
        )

    dist, passing = jax.lax.cond(jnp.any(scan_mask), do_scan, no_scan, None)
    return PlannedBatch(
        mode=plans.mode,
        est_sel=plans.est_sel,
        run_total=plans.run_total,
        ids=plans.ids,
        mask=scan_mask,
        dist=dist,
        passing=passing,
    )
