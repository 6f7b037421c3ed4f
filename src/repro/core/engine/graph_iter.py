"""G.NEXT — the pull-based graph iterator (Algorithm 2).

Owns graph entry selection and the passrate-adaptive beam expansion
(one-hop / two-hop / pivot).  :func:`step` advances the iterator by one
driver round and reports whether the relational iterator should be pulled
next, so the driver loop is just Algorithm 1's coordination.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs.profiling import staged

from .. import predicate as P
from . import state as S


def seed_entries(index, rank, pm):
    """SELECTENTRYPOINT (Alg. 2 line 8).

    HNSW descends its upper layers to locate a good entry; our flat build
    instead seeds with the medoids of the ``entry_fanout`` nearest IVF
    clusters — same role, and robust when clusters straddle modes.  The
    global-medoid graph entry rides along as a fallback.
    """
    if pm.adaptive_entry:
        fan = min(pm.entry_fanout, index.nlist)
        entries = index.medoids[rank[:fan]].astype(jnp.int32)
        return jnp.concatenate([entries, index.graph.entry.astype(jnp.int32)[None]])
    return index.graph.entry.astype(jnp.int32)[None]


@staged("engine/gnext")
def expand(index, q, pred, st: S.EngineState, pm, backend) -> S.EngineState:
    """Pop the best `beam` shared-queue candidates and expand per
    neighbourhood passrate (Algorithm 2 lines 12-17; beam == 1 is the
    paper-faithful per-candidate loop)."""
    n = index.n_records
    m = index.graph.degree
    w = pm.beam
    heads_d, heads_i, cand = st.cand.pop(w)
    head_ok = jnp.isfinite(heads_d)
    st = st._replace(cand=cand)

    nbrs = index.graph.neighbors[jnp.clip(heads_i, 0, n - 1)].reshape(-1)  # (W*M,)
    valid = (nbrs < n) & jnp.repeat(head_ok, m)
    safe = jnp.where(valid, nbrs, n)
    npass = P.evaluate(pred, index.attrs[safe]) & valid
    sel = jnp.sum(npass) / jnp.maximum(jnp.sum(valid), 1)

    unvis = valid & ~S.is_visited(st.visited, safe)
    wm = w * m
    vl = wm + pm.k2

    def one_hop(_):
        mask = unvis & npass if pm.in_filter else unvis
        ids = jnp.concatenate([nbrs, jnp.full((pm.k2,), n, jnp.int32)])
        mk = jnp.concatenate([mask, jnp.zeros((pm.k2,), bool)])
        return ids, mk

    def two_hop(_):
        nbrs2 = index.graph.neighbors[safe].reshape(-1)  # (W*M*M,)
        valid2 = (nbrs2 < n) & jnp.repeat(valid, m)
        safe2 = jnp.where(valid2, nbrs2, n)
        pass2 = P.evaluate(pred, index.attrs[safe2]) & valid2
        unvis2 = pass2 & ~S.is_visited(st.visited, safe2)
        unvis2 = S.dedup_new(nbrs2, unvis2)
        # pick a bounded subset of passing two-hop neighbours
        score = unvis2.astype(jnp.float32)
        _, top_idx = jax.lax.top_k(score, pm.k2)
        sel_ids = nbrs2[top_idx]
        sel_mk = unvis2[top_idx]
        ids = jnp.concatenate([nbrs, sel_ids])
        mk = jnp.concatenate([unvis & npass, sel_mk])
        return ids, mk

    def none_(_):
        return jnp.full((vl,), n, jnp.int32), jnp.zeros((vl,), bool)

    if pm.in_filter:  # NaviX-style: never pivots, two-hop when sel < alpha
        branch = jnp.where(sel >= pm.alpha, 0, 1)
    else:
        branch = jnp.where(sel >= pm.alpha, 0, jnp.where(sel >= pm.beta, 1, 2))
    ids, mk = jax.lax.switch(branch, [one_hop, two_hop, none_], None)
    st = S.visit(index, q, pred, st, ids, mk, pm, backend)
    return st._replace(last_sel=sel)


@staged("engine/gnext")
def step(index, q, pred, st: S.EngineState, pm, backend):
    """One G.NEXT round of the driver loop.

    Returns ``(state, need_b)`` where ``need_b`` asks the driver to pull
    B.NEXT: the graph broke on low passrate (Alg. 2 line 17), converged at
    the efs cap, or ran out of candidates.
    """
    queue_empty, gstop = S.graph_frontier(st, pm)
    # gstop == Alg. 2 line 13: this G.NEXT round converged at the current
    # efs. Return <= k found records to the global TopQ, then ExpandSearch
    # widens efs for the next round.
    st = jax.lax.cond(gstop, lambda s: S.credit(s, pm.k), lambda s: s, st)
    new_efs = jnp.minimum(st.efs + pm.stepsize, pm.ef_cap)
    at_cap = st.efs >= pm.ef_cap
    st = st._replace(efs=jnp.where(gstop & ~at_cap, new_efs, st.efs))
    do_pop = ~gstop
    st = S.run_if(do_pop, lambda s: expand(index, q, pred, s, pm, backend), st)
    low_sel = do_pop & (st.last_sel < pm.beta)
    # low-sel break is also a G.NEXT round boundary (Alg. 2 line 17)
    st = jax.lax.cond(low_sel, lambda s: S.credit(s, pm.k), lambda s: s, st)
    need_b = low_sel | (gstop & at_cap) | queue_empty
    return st, need_b


def dead(st: S.EngineState, pm) -> jax.Array:
    """No graph progress is possible anymore (stall detection input)."""
    queue_empty, gstop = S.graph_frontier(st, pm)
    return (gstop & (st.efs >= pm.ef_cap)) | queue_empty
