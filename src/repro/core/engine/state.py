"""State & queue layer of the Compass execution engine.

Everything the two iterators (G.NEXT / B.NEXT) and the driver loop share
lives here: the fixed-capacity sorted-array queue abstraction, the fused
search state, the VISIT state update (Algorithm 4 minus the scoring, which
a :mod:`~repro.core.engine.backend` provides), and the credit/round-pacing
bookkeeping of Algorithm 1.

Queue representation (DESIGN.md §Adaptation): a priority queue on TPU is a
fixed-capacity ascending-sorted array with ``+inf`` marking empty slots.
``RecycQ`` of Algorithm 2 is *implicit*: the graph-top queue always holds up
to its full capacity and the live prefix is ``efs`` — enlarging ``efs``
re-admits exactly the entries the paper's RecycQ would replay.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.obs.profiling import stage_scope, staged

INF = jnp.inf


class FixedQueue(NamedTuple):
    """Fixed-capacity priority queue as a sorted array (+inf == empty slot).

    Shared by the candidate queue (CandQ), the graph-top queue (TopQ width
    control) and the filtered result queue (the global TopQ of Alg. 1).
    Being a NamedTuple of arrays it is a JAX pytree, so it threads through
    ``lax.while_loop`` / ``vmap`` unchanged.
    """

    d: jax.Array  # (cap,) f32, ascending; +inf = empty
    i: jax.Array  # (cap,) int32 record ids; sentinel where empty

    @classmethod
    def full(cls, cap: int, sentinel: int) -> "FixedQueue":
        return cls(
            jnp.full((cap,), INF, jnp.float32),
            jnp.full((cap,), sentinel, jnp.int32),
        )

    @property
    def cap(self) -> int:
        return self.d.shape[0]

    @staged("engine/sort")
    def merge(self, nd: jax.Array, ni: jax.Array) -> "FixedQueue":
        """Merge new (dist, id) entries, keeping the best ``cap``."""
        d = jnp.concatenate([self.d, nd])
        i = jnp.concatenate([self.i, ni])
        order = jnp.argsort(d)
        return FixedQueue(d[order[: self.cap]], i[order[: self.cap]])

    def count(self) -> jax.Array:
        """Number of live (finite) entries."""
        return jnp.sum(jnp.isfinite(self.d)).astype(jnp.int32)

    @staged("engine/sort")
    def pop(self, w: int) -> tuple[jax.Array, jax.Array, "FixedQueue"]:
        """Remove the best ``w`` entries; returns (dists, ids, rest)."""
        heads_d, heads_i = self.d[:w], self.i[:w]
        d = self.d.at[:w].set(INF)
        order = jnp.argsort(d)
        return heads_d, heads_i, FixedQueue(d[order], self.i[order])


@staged("engine/sort")
def dedup_new(ids: jax.Array, mask: jax.Array) -> jax.Array:
    """Mask out later duplicate ids within a visit list."""
    ids_masked = jnp.where(mask, ids, jnp.iinfo(jnp.int32).max)
    sort_idx = jnp.argsort(ids_masked)
    s = ids_masked[sort_idx]
    dup_sorted = jnp.concatenate([jnp.zeros((1,), bool), s[1:] == s[:-1]])
    dup = jnp.zeros_like(dup_sorted).at[sort_idx].set(dup_sorted)
    return mask & ~dup


def visited_words(n: int) -> int:
    """Words of one lane's visited bitmap over the ids ``0..n`` (the
    sentinel ``n`` has a bit of its own): ``ceil((n + 1) / 32)`` rounded up
    to a multiple of 1024.  Whole 1024-word tiles let the vmapped
    ``(B, W)`` table and the flat form its scatter takes share one layout on
    the TPU; a ``(B, n + 1)`` bool table was relaid out, lane by lane, on
    every visit (DESIGN.md §Perf)."""
    return -(-(n + 1) // (32 * 1024)) * 1024


def is_visited(visited: jax.Array, ids: jax.Array) -> jax.Array:
    """Whether each of ``ids`` has its bit set: bit ``id & 31`` of word
    ``id >> 5``.  Meaningful for ids in ``0..n``; the gather clamps any
    other, so a caller masks those out."""
    word = visited[ids >> 5]
    return ((word >> (ids & 31).astype(jnp.uint32)) & 1) == 1


def mark_visited(visited: jax.Array, ids: jax.Array, mask: jax.Array) -> jax.Array:
    """Set the bits of ``ids`` (in ``0..n``) where ``mask``.

    One scatter-add of the bits into their words.  Adding a bit is OR-ing
    it only if it is not set yet and no other masked id sets it too, so the
    caller guarantees: the masked ids are distinct, and none is visited
    yet.  Masked-out entries add 0 and change nothing, wherever they
    point."""
    bits = jnp.where(mask, jnp.uint32(1) << (ids & 31).astype(jnp.uint32), jnp.uint32(0))
    return visited.at[ids >> 5].add(bits)


class SearchStats(NamedTuple):
    n_dist: jax.Array  # full-precision distance computations (paper #Comp;
    # includes the quantized tier's stage-two rerank rows when those read
    # the float32 table — rerank="full")
    n_cdist: jax.Array  # centroid distance computations; 0 when the exact
    # centroid ranking has no consumer (use_btree=False and non-adaptive
    # entry) and the scan is skipped entirely
    n_steps: jax.Array  # loop iterations
    n_bcalls: jax.Array  # relational injections
    n_clusters_ranked: jax.Array  # clusters actually opened by B.NEXT
    n_adc: jax.Array  # quantized (ADC table-lookup) scores — stage one of
    # the quantized tier; 0 whenever CompassParams.quant is off
    n_rerank: jax.Array  # stage-two exact distances of the quantized tier
    n_pass: jax.Array  # predicate-passing AND live rows among the scored
    # ones (visit admissions + prefilter adoptions + delta scan passes);
    # n_pass / rows-examined is the *measured* selectivity an explain
    # trace reports next to the planner's estimate (obs/trace.py)
    mode: jax.Array  # planner execution mode (planner.plan.MODE_NAMES index);
    # COOPERATIVE when the planner is off
    efs_final: jax.Array
    est_sel: jax.Array  # f32 planner-estimated selectivity; -1.0 when the
    # planner is off (explain renders that as "no estimate")
    run_total: jax.Array  # int32 planner-estimated candidate run rows (the
    # cost-model input behind the mode choice); -1 when the planner is off


class SearchResult(NamedTuple):
    ids: jax.Array  # (k,) int32, padded with N
    dists: jax.Array  # (k,) f32, padded with +inf
    stats: SearchStats


class EngineState(NamedTuple):
    """The fused per-query search state threaded through the driver loop."""

    cand: FixedQueue  # shared candidate queue (CandQ)
    gtop: FixedQueue  # graph-internal top queue (width control; unfiltered)
    efs: jax.Array  # progressive search width
    res: FixedQueue  # filtered result queue (the global TopQ of Alg. 1)
    visited: jax.Array  # (visited_words(N),) uint32 bitmap over ids 0..N
    # clustered B+-tree iterator state (owned by btree_iter)
    rank: jax.Array  # (nlist,) clusters in centroid-distance order
    rank_pos: jax.Array  # cursor into `rank`
    term_beg: jax.Array  # (T,) cursors into order arrays (global positions)
    term_end: jax.Array
    b_exhausted: jax.Array
    # round-pacing bookkeeping (Alg. 1)
    returned: jax.Array  # records handed to the global TopQ so far
    stalled: jax.Array
    last_sel: jax.Array
    stats: SearchStats


@staged("engine/visit")
def visit(index, q, pred, st: EngineState, ids, mask, pm, backend) -> EngineState:
    """Algorithm 4 over a fixed-size visit list.

    Scoring (distance + predicate) is delegated to ``backend``; this
    function owns the state update: dedup, visited marking, and the pushes
    into the shared queue, the graph top queue, and (for predicate-passing
    records) the filtered result queue.
    """
    n = index.n_records
    mask = dedup_new(ids, mask)
    mask = mask & ~is_visited(st.visited, ids)
    safe = jnp.where(mask, ids, n).astype(jnp.int32)
    # One fused scoring call per visit batch: distance + DNF predicate +
    # tombstone mask + queue admission.  `dist` feeds the traversal queues
    # (a dead record keeps routing — it stays in cand/gtop so traversal
    # flows through it); `admit` is +inf unless the row is valid, passes
    # the predicate AND is alive, so merging it into the result queue is
    # exactly the old visit_scores -> live-AND -> where sequence (the ref
    # backend literally composes that sequence; the pallas backend runs the
    # kernels/visit_step.py fused kernel unless pm.fused_visit is off).
    dist, admit = backend.visit_step(
        index, q, pred, safe, mask, pm.metric, fused=pm.fused_visit,
        rows_per_step=pm.shape.visit_rb or None,
    )
    visited = mark_visited(st.visited, safe, mask)  # distinct, unvisited
    cand = st.cand.merge(dist, safe)
    gtop = st.gtop.merge(dist, safe)
    res = st.res.merge(admit, safe)
    # A quant-adapted backend (backend.QuantAdapter) scores visits through
    # the ADC tables, so the work lands in n_adc, not the full-precision
    # #Comp counter.  Trace-time branch: counts_as is a plain attribute.
    # `admit` is finite exactly for valid, predicate-passing, live rows —
    # summing its finite count measures the passrate the planner estimated
    if getattr(backend, "counts_as", "dist") == "adc":
        stats = st.stats._replace(
            n_adc=st.stats.n_adc + jnp.sum(mask),
            n_pass=st.stats.n_pass + jnp.sum(jnp.isfinite(admit)).astype(jnp.int32),
        )
    else:
        stats = st.stats._replace(
            n_dist=st.stats.n_dist + jnp.sum(mask),
            n_pass=st.stats.n_pass + jnp.sum(jnp.isfinite(admit)).astype(jnp.int32),
        )
    return st._replace(
        cand=cand,
        gtop=gtop,
        res=res,
        visited=visited,
        stats=stats,
    )


def run_if(pred, f, st: EngineState) -> EngineState:
    """``lax.cond(pred, f, identity, st)`` for a per-lane ``pred``, as a
    select.  The engine runs under ``vmap``, where a cond with a batched
    predicate runs both branches and selects anyway — but its batching
    rule first broadcasts every operand to the batch, the index included.
    A Pallas kernel takes the corpus as an HBM operand, so that broadcast
    would be materialized: B copies of the corpus.  Running ``f`` and
    selecting computes the same values and keeps the index unbatched.
    Use it for the branches that score rows (they reach the kernels)."""
    new = f(st)
    with stage_scope("engine/select"):
        return jax.tree.map(lambda a, b: jnp.where(pred, a, b), new, st)


def res_count(st: EngineState) -> jax.Array:
    return st.res.count()


def credit(st: EngineState, batch: int) -> EngineState:
    """A round boundary: the iterator hands <= batch of its found-but-
    unreturned records to Alg. 1's global TopQ (ResQ/RelQ pops)."""
    give = jnp.minimum(jnp.int32(batch), res_count(st) - st.returned)
    return st._replace(returned=st.returned + jnp.maximum(give, 0))


def graph_frontier(st: EngineState, pm) -> tuple[jax.Array, jax.Array]:
    """(queue_empty, gstop): has the shared queue drained, and has this
    G.NEXT round converged at the current efs (Alg. 2 line 13)."""
    head_d = st.cand.d[0]
    queue_empty = ~jnp.isfinite(head_d)
    worst = st.gtop.d[jnp.minimum(st.efs, pm.ef_cap) - 1]
    return queue_empty, queue_empty | (head_d > worst)
