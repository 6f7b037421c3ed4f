"""The Compass driver loop — Algorithm 1's G.NEXT/B.NEXT coordination as
one fused, batched ``lax.while_loop``.

Faithfulness notes (full discussion in DESIGN.md §Adaptation):

* The paper structures the search as two pull-based iterators (G.NEXT /
  B.NEXT) coordinating through a shared candidate queue.  On TPU, function
  calls are free but *dynamic shapes are not*, so the two iterators become
  two branches of a single fixed-shape loop body; the shared candidate
  queue, visited set, progressive ``efs``, passrate-adaptive expansion,
  round-paced result returns and relational injection are all preserved
  with identical candidate flow.  The iterators live in graph_iter.py /
  btree_iter.py behind the same ``step(state) -> state`` shape; scoring is
  pluggable via backend.py (``"ref"`` jnp gathers vs ``"pallas"`` fused
  kernels); this module is only the coordination.
* The paper's cluster graph G' (§IV.C) is replaced by an exact centroid
  ranking — one MXU matmul at OPEN — consumed through a cursor, preserving
  the on-demand semantics (see index.py docstring).
* Visited is a packed bitmap, one uint32 word per 32 ids
  (``state.mark_visited`` / ``state.is_visited``): the TPU keeps a
  ``(B, N+1)`` bool table in a tiling it must relay out on every visit
  (DESIGN.md §Perf).

The same loop, parameterized by :class:`CompassParams`, also implements the
paper's baselines and ablations:
  * ``in_filter=True, use_btree=False``  -> NaviX/ACORN-style in-filtering.
  * ``use_btree=False``                  -> plain progressive HNSW
    (post-filtering building block).
  * ``use_graph=False``                  -> CompassRelational ablation.
  * index built with ``nlist=1``         -> CompassGraph ablation.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from typing import TYPE_CHECKING

from repro.obs.profiling import stage_scope, staged

from .. import predicate as P
from ..planner import plan as qplan
from ..quant import encode as Q
from ..quant.params import QuantParams
from ..quant.rerank import rerank_batch
from . import btree_iter, graph_iter
from . import state as S
from .backend import QuantAdapter, VisitBackend, resolve_backend
from .state import EngineState, FixedQueue, SearchResult, SearchStats

if TYPE_CHECKING:  # runtime import would cycle (index -> planner -> engine)
    from ..index import CompassIndex

#: Bumped whenever the engine's candidate flow changes in a way that could
#: move benchmark trajectories (recorded in BENCH_*.json by benchmarks/).
#: engine/2: cost-based planner (per-query PREFILTER/COOPERATIVE/POSTFILTER
#: dispatch) + the centroid scan is skipped when nothing consumes it.
#: engine/3: mutable-index tombstone masking — dead records keep routing in
#: the visit loop but are ANDed out of the result queue and the PREFILTER
#: adoption (no-op for immutable indices: index.live is None).
#: engine/4: quantized tier — with CompassParams.quant set, stage one runs
#: the loop at ef*refine_factor with ADC scoring (kernels/pq_score) and
#: stage two reranks the survivors exactly; quant=None paths are bitwise
#: unchanged (trace-time branch on index.qvecs / pm.quant).
#: engine/5: fused visit step — state.visit scores through the single
#: backend.visit_step surface (pallas: one kernels/visit_step.py call for
#: gather + distance + predicate + tombstone + admission); ip runs on the
#: kernels (no more ref fallback) and "cos" is rewritten to ip over
#: normalized rows at entry.  backend="ref" and fused_visit=False paths
#: stay bitwise identical to engine/4.
ENGINE_VERSION = "engine/5"


@dataclasses.dataclass(frozen=True)
class ShapePolicy:
    """Every steady-state compiled shape in one frozen, hashable config.

    ``compass_search`` / ``mutable_search`` are jitted over static shapes:
    each distinct row count, delta capacity, queue width or kernel block is
    a fresh XLA program.  The shape-affecting knobs used to be scattered
    (row counts implicit in the fold, ``delta_cap`` on MutableIndex, ``ef``
    on CompassParams, block pins in env vars); this object gathers them so
    the serving executable cache can key on *one* value and the mutable
    path can hold every shape fixed across compaction epochs
    (DESIGN.md §Mutability, bucket-fold contract):

      * **row buckets** — compaction folds pad the base to the next
        power-of-two row count (>= ``min_rows``) with dead, tombstoned
        rows, so churn that stays within a bucket re-traces nothing.
      * **delta capacity** — ``delta_cap`` (0 = adopt the MutableIndex
        constructor argument) is a compiled shape; owning it here makes it
        part of the policy identity rather than an ad-hoc constructor int.
      * **ef / refine widths** — ``ef_step`` rounds ``ef`` (and therefore
        the quant-widened ``ef * refine_factor`` stage-one width) up to a
        multiple, collapsing near-miss configurations onto shared
        executables.  Rounding *widens* the search — results are those of
        the rounded ``ef``, never an approximation of the requested one.
      * **fused-visit block** — ``visit_rb`` pins the visit-step kernel's
        rows-per-step (0 = autotune / ``REPRO_PALLAS_BLOCK_VISIT_STEP``),
        making the block choice part of the params identity instead of
        ambient process state.  Block choice never affects results.

    ``ef`` / ``refine_factor`` here are construction-time overrides
    (0 = keep the CompassParams / QuantParams field): ``CompassParams.
    __post_init__`` adopts a non-zero value into the legacy field and
    normalizes it back to 0, so the legacy fields stay the single source
    of truth and existing call sites / BENCH provenance keys keep working.
    """

    bucket_rows: bool = True  # pad compaction folds to power-of-two buckets
    min_rows: int = 1024  # smallest row bucket a fold pads to
    delta_cap: int = 0  # delta-segment capacity; 0 = constructor default
    ef_step: int = 0  # round ef up to a multiple; 0 = exact (no rounding)
    visit_rb: int = 0  # fused visit-step rows-per-step pin; 0 = autotune
    ef: int = 0  # construction-time override of CompassParams.ef
    refine_factor: int = 0  # construction-time override of quant.refine_factor

    def row_bucket(self, n_live: int) -> int:
        """Padded base row count for ``n_live`` real rows (identity when
        ``bucket_rows`` is off)."""
        if not self.bucket_rows:
            return n_live
        return max(self.min_rows, 1 << max(0, n_live - 1).bit_length())

    def bucket_ef(self, ef: int) -> int:
        """``ef`` rounded up to the next ``ef_step`` multiple (identity
        when ``ef_step`` is 0)."""
        if self.ef_step <= 0:
            return ef
        return -(-ef // self.ef_step) * self.ef_step

    def resolve_delta_cap(self, default: int) -> int:
        return self.delta_cap if self.delta_cap > 0 else int(default)


@dataclasses.dataclass(frozen=True)
class CompassParams:
    k: int = 10  # results to return
    ef: int = 64  # target size of the filtered result queue (paper `ef`)
    alpha: float = 0.3  # one-hop passrate threshold (paper default)
    beta: float = 0.05  # two-hop / pivot passrate threshold (paper default)
    efs0: int = 16  # initial progressive search width
    stepsize: int = 16  # progressive efs increment (paper `stepsize`)
    ef_cap: int = 0  # max efs; 0 => 2 * ef + 32
    cand_cap: int = 0  # shared queue capacity; 0 => ef_cap + 64
    efi: int = 32  # records fetched per B.NEXT (paper `efi`)
    k2: int = 16  # two-hop visit budget per expansion
    max_steps: int = 0  # hard iteration budget; 0 => heuristic
    metric: str = "l2"
    use_graph: bool = True  # False => CompassRelational ablation
    use_btree: bool = True  # False => pure graph (NaviX / HNSW modes)
    in_filter: bool = False  # True => NaviX-style distance-only-if-passing
    adaptive_entry: bool = True  # IVF-guided entry (False: global medoid)
    entry_fanout: int = 4  # medoids of the top-R clusters seed the traversal
    cluster_tries: int = 8  # clusters examined per B step at most
    beam: int = 1  # candidates popped+expanded per loop step (DESIGN.md
    # §Perf: beam>1 amortizes the per-step queue sorts and raises the
    # arithmetic intensity of each visit batch; passrate adaptivity is
    # evaluated over the pooled beam neighborhood instead of per candidate)
    backend: str = "auto"  # "ref" | "pallas" | "auto" (pallas on TPU)
    fused_visit: bool = True  # route VISIT through the fused visit-step
    # kernel on the pallas backend (kernels/visit_step.py).  False keeps
    # the unfused filter_distance + live-gather + select sequence — same
    # results bitwise, one extra kernel launch + two HBM round-trips per
    # visit batch (the parity suite asserts on/off equality).
    planner: bool = False  # cost-based per-query mode selection (DESIGN.md
    # §Planner; requires index.astats — i.e. an index built by build_index)
    prefilter_cap: int = 0  # max materialized run rows for PREFILTER;
    # 0 => 8 * ef (the cost-model crossover, see planner/plan.py)
    postfilter_min_sel: float = 0.9  # POSTFILTER eligible above this
    # estimated selectivity ("selectivity ≈ 1": the filter is near-vacuous)
    quant: QuantParams | None = None  # quantized-tier search (DESIGN.md
    # §Quantization; requires index.qvecs — i.e. quantize_index).  None
    # (the default) keeps every program bitwise identical to exact search.
    shape: ShapePolicy = ShapePolicy()  # compiled-shape policy (row/ef
    # buckets, delta capacity, kernel block pin).  Part of hash/eq, so it
    # keys every executable cache that keys on CompassParams.

    def __post_init__(self):
        # Adopt ShapePolicy's construction-time overrides into the legacy
        # fields, then normalize them back to 0.  The normalization makes
        # __post_init__ idempotent under dataclasses.replace — the quant
        # stage does replace(pm, ef=ef*rf, k=ef*rf), and a sticky nonzero
        # shape.ef would silently clobber the widened width on re-init.
        sp = self.shape
        if sp.ef:
            object.__setattr__(self, "ef", sp.ef)
        if sp.refine_factor and self.quant is not None:
            object.__setattr__(
                self,
                "quant",
                dataclasses.replace(self.quant, refine_factor=sp.refine_factor),
            )
        if sp.ef or sp.refine_factor:
            object.__setattr__(
                self, "shape", dataclasses.replace(sp, ef=0, refine_factor=0)
            )
        # ef rounding happens here, not in resolved(): two params that
        # land in the same ef bucket must already be ==/hash-equal so the
        # jit trace cache and serving executable keys collapse them.
        if sp.ef_step > 0:
            object.__setattr__(self, "ef", sp.bucket_ef(self.ef))

    def resolved(self) -> "CompassParams":
        ef_cap = self.ef_cap or 2 * self.ef + 32
        cand_cap = self.cand_cap or ef_cap + 64
        max_steps = self.max_steps or (4 * ef_cap + 8 * self.ef + 64)
        prefilter_cap = self.prefilter_cap or 8 * self.ef
        return dataclasses.replace(
            self,
            ef_cap=ef_cap,
            cand_cap=cand_cap,
            max_steps=max_steps,
            prefilter_cap=prefilter_cap,
        )


@staged("engine/open")  # everything before the loop: ranking, state, seeds
def _search_one(
    index: CompassIndex,
    q,
    cdists,
    pred: P.Predicate,
    pm: CompassParams,
    backend: VisitBackend,
    needs_rank: bool = True,
    plan: "qplan.PlannedBatch | None" = None,
    lut=None,
) -> SearchResult:
    n = index.n_records
    nlist = index.nlist
    T = pred.lo.shape[0]
    chosen = P.chosen_attrs(pred)
    if lut is not None:
        # quantized tier: route VISIT scoring through this query's ADC
        # table; candidate generation (iterators, queues) is untouched
        backend = QuantAdapter(backend, lut)

    # B.OPEN / G.OPEN: exact centroid ranking shared by the relational
    # iterator and the adaptive entry.  `cdists` is computed batched in
    # compass_search (outside the per-query vmap) so the pallas backend's
    # ivf_score kernel sees the full (B, C) blocked problem.
    rank = jnp.argsort(cdists).astype(jnp.int32)
    mode = jnp.int32(qplan.COOPERATIVE) if plan is None else plan.mode

    zero = jnp.int32(0)
    stats = SearchStats(
        n_dist=zero,
        n_cdist=jnp.int32(nlist if needs_rank else 0),
        n_steps=zero,
        n_bcalls=zero,
        n_clusters_ranked=zero,
        n_adc=zero,
        n_rerank=zero,
        n_pass=zero,
        mode=mode,
        efs_final=jnp.int32(pm.efs0),
        # planner provenance rides in the stats so an explain trace can
        # compare estimate vs. measurement without re-running the planner
        # (obs/trace.py); -1 marks "planner off, no estimate"
        est_sel=jnp.float32(-1.0) if plan is None else plan.est_sel,
        run_total=jnp.int32(-1) if plan is None else plan.run_total,
    )
    st = EngineState(
        cand=FixedQueue.full(pm.cand_cap, n),
        gtop=FixedQueue.full(pm.ef_cap, n),
        efs=jnp.int32(pm.efs0),
        res=FixedQueue.full(pm.ef, n),
        visited=jnp.zeros((S.visited_words(n),), jnp.uint32),
        rank=rank,
        rank_pos=jnp.int32(0),
        term_beg=jnp.zeros((T,), jnp.int32),
        term_end=jnp.zeros((T,), jnp.int32),
        # PREFILTER and POSTFILTER never pull B.NEXT: the former already
        # holds the exact result, the latter is the graph-dominant plan.
        b_exhausted=jnp.asarray(not pm.use_btree) | (mode != qplan.COOPERATIVE),
        returned=jnp.int32(0),
        stalled=jnp.asarray(False),
        last_sel=jnp.float32(1.0),
        stats=stats,
    )

    if plan is not None:
        # PREFILTER: the planner materialized + pre-scored every candidate
        # run row (batched scan, hoisted out of the vmap); adopt the exact
        # top-ef here and retire the query before the loop starts.
        def run_prefilter(s: EngineState) -> EngineState:
            safe = jnp.where(plan.mask, plan.ids, n).astype(jnp.int32)
            # plan.mask is deduplicated and nothing is visited yet
            visited = S.mark_visited(s.visited, safe, plan.mask)
            passing = plan.passing
            if index.live is not None:  # tombstoned rows stay out of results
                passing = passing & index.live[safe]
            res = s.res.merge(jnp.where(passing, plan.dist, S.INF), safe)
            n_pass = s.stats.n_pass + jnp.sum(passing).astype(jnp.int32)
            if lut is not None:  # the planner scan scored through ADC tables
                stats2 = s.stats._replace(
                    n_adc=s.stats.n_adc + jnp.sum(plan.mask), n_pass=n_pass
                )
            else:
                stats2 = s.stats._replace(
                    n_dist=s.stats.n_dist + jnp.sum(plan.mask), n_pass=n_pass
                )
            return s._replace(
                res=res,
                visited=visited,
                returned=jnp.int32(pm.ef),
                stalled=jnp.asarray(True),
                stats=stats2,
            )

        st = jax.lax.cond(mode == qplan.PREFILTER, run_prefilter, lambda s: s, st)

    if pm.use_graph:
        entries = graph_iter.seed_entries(index, rank, pm)
        seed_mask = jnp.ones(entries.shape, bool) & (mode != qplan.PREFILTER)
        st = S.visit(index, q, pred, st, entries, seed_mask, pm, backend)

    def cond(st: EngineState):
        return (
            (st.returned < pm.ef)
            & (st.stats.n_steps < pm.max_steps)
            & ~st.stalled
        )

    def body(st: EngineState):
        if pm.use_graph:
            st, need_b = graph_iter.step(index, q, pred, st, pm, backend)
        else:
            need_b = jnp.asarray(True)

        if pm.use_btree:

            def do_b(s):
                s = btree_iter.step(index, q, pred, chosen, s, pm, backend)
                return S.credit(s, max(1, pm.k // 2))  # Alg. 3 line 20: k/2 batch

            st = S.run_if(need_b & ~st.b_exhausted, do_b, st)
        # stall: nothing can make progress anymore
        graph_dead = graph_iter.dead(st, pm) if pm.use_graph else jnp.asarray(True)
        stalled = graph_dead & st.b_exhausted
        # a stalled search still flushes whatever it found
        st = jax.lax.cond(stalled, lambda s: S.credit(s, pm.ef), lambda s: s, st)
        st = st._replace(
            stalled=stalled,
            stats=st.stats._replace(n_steps=st.stats.n_steps + 1, efs_final=st.efs),
        )
        return st

    with stage_scope("engine/loop"):
        st = jax.lax.while_loop(cond, body, st)
        final_stats = st.stats._replace(n_clusters_ranked=st.rank_pos)
        return SearchResult(st.res.i[: pm.k], st.res.d[: pm.k], final_stats)


@functools.partial(jax.jit, static_argnames=("pm",))
def compass_search_jit(
    index: CompassIndex,
    queries: jax.Array,
    pred: P.Predicate,
    pm: CompassParams,
    luts: jax.Array | None = None,
) -> SearchResult:
    """The jitted search program behind :func:`compass_search` — use it
    directly for AOT paths (``.lower(...).compile()``, the serving
    executable cache) and jit-cache accounting (``._cache_size()``).

    With ``pm.quant`` set (and a quantized index), this is the two-stage
    quantized search: stage one runs the ordinary loop at
    ``ef * refine_factor`` with ADC scoring, stage two reranks the
    survivors exactly and returns the top ``pm.k`` (quant/rerank.py).
    ``luts`` optionally supplies the per-query ADC tables (built here when
    omitted) — the mutable fan-out passes its own so base and delta share
    one table build per query.
    """
    if pm.metric == "cos":
        # cosine == inner product over unit-norm rows: normalize the query
        # batch here and run the whole engine (planner, quant tables,
        # kernels) as "ip" — one rewrite point, no per-kernel cos variants.
        # Requires an index built with BuildConfig(metric="cos"), which
        # normalized the corpus rows at build time.
        from ..distances import normalize_rows

        queries = normalize_rows(queries)
        pm = dataclasses.replace(pm, metric="ip")
    quant = pm.quant is not None
    if quant and index.qvecs is None:
        raise ValueError(
            "CompassParams.quant requires a quantized index "
            "(attach codes with core.quant.quantize_index first)"
        )
    k_out = pm.k
    if quant:
        # stage one: widen the result queue so the approximate ADC ordering
        # still captures the true top-k for stage two to recover
        rf = pm.quant.refine_factor
        pm = dataclasses.replace(pm, ef=pm.ef * rf, k=pm.ef * rf)
    pm = pm.resolved()
    backend = resolve_backend(pm.backend)
    # One blocked (B, C) centroid scan for the whole batch (B.OPEN / G.OPEN)
    # — skipped entirely when nothing consumes the ranking (pure-graph
    # ablations with non-adaptive entry), so SearchStats.n_cdist is the true
    # count rather than an unconditional nlist.  The coarse layer stays
    # full-precision under quantization (standard IVF-PQ).
    needs_rank = pm.use_btree or (pm.use_graph and pm.adaptive_entry)
    with stage_scope("engine/open"):
        if needs_rank:
            cdists = backend.centroid_scores(index, queries, pm.metric)
        else:
            cdists = jnp.zeros((queries.shape[0], index.nlist), jnp.float32)
    if quant:
        # per-query ADC tables, built batched outside the vmap
        if luts is None:
            luts = Q.build_luts(index.qvecs, queries, pm.metric)  # (B, m, ks)
    else:
        luts = None
    planned = (
        qplan.plan_batch(index, queries, pred, pm, backend, luts=luts)
        if pm.planner
        else None
    )
    # one vmap for all planner x quant combinations: None is a leafless
    # pytree, so an absent plan / lut passes through the batch axes
    # untouched and _search_one's trace-time `is None` branches see
    # exactly what a narrower call signature would have passed
    res = jax.vmap(
        lambda q, cd, lo, hi, pl, lut: _search_one(
            index, q, cd, P.Predicate(lo, hi), pm, backend, needs_rank, pl, lut
        )
    )(queries, cdists, pred.lo, pred.hi, planned, luts)
    if quant:
        res = rerank_batch(
            index, queries, pred, res, k_out, pm.metric, backend, pm.quant.rerank
        )
    return res


def compass_search(
    index: CompassIndex,
    queries: jax.Array,
    pred: P.Predicate,
    pm: CompassParams,
    luts: jax.Array | None = None,
    *,
    explain: bool = False,
):
    """Batched filtered search. queries: (B, d); pred arrays: (B, T, A).

    The public entry point: runs :func:`compass_search_jit` (see its
    docstring for the quantized two-stage semantics) and, with
    ``explain=True``, additionally returns one
    :class:`~repro.obs.trace.QueryTrace` per query::

        res, traces = compass_search(index, q, pred, pm, explain=True)
        print(repro.compass.explain(traces))

    Explain is bitwise-free: every field a trace needs already rides in
    the device-side ``SearchStats``, so the traced program — and thus the
    jit/executable cache key and every result bit — is identical with and
    without the flag; ``explain=True`` merely materializes the stats
    host-side afterwards.  The flag is host-only and must not be used
    under an outer ``jax.jit`` (the default ``False`` path is
    transparent to tracing — ``mutable_search`` relies on that).
    """
    res = compass_search_jit(index, queries, pred, pm, luts)
    if not explain:
        return res
    from repro.obs.trace import build_traces  # lazy: obs sits above the engine

    return res, build_traces(res, pm)
