"""B.NEXT — the pull-based relational iterator (Algorithm 3).

Pulls predicate-passing records from the clustered B+-trees (per-attribute
sorted runs, see clustered_attrs.py) of the clusters nearest to the query,
on demand, through the ranked-cluster cursor stored in the engine state
(``rank`` / ``rank_pos`` / ``term_beg`` / ``term_end`` / ``b_exhausted``).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.obs.profiling import staged

from .. import predicate as P
from ..clustered_attrs import run_position
from . import state as S


def advance(index, pred, chosen, st: S.EngineState, tries: int) -> S.EngineState:
    """Advance the ranked-cluster cursor when no term has rows left.

    Opens the next clusters of ``rank`` in turn, at most ``tries`` of them,
    until one holds a row in some term's range, and points the per-term
    cursors at that cluster's runs.  Running past the last cluster marks
    the iterator exhausted and leaves the cursors alone.

    All ``tries`` candidate clusters are probed at once (one
    :func:`run_position` loop over ``(side, try, term)``) and the first
    non-empty one is picked, which is what opening them one by one gives.
    Only the four cursor leaves are selected, so no ``lax.cond`` broadcasts
    the runs or the state to every lane under ``vmap``.
    """
    ca = index.cattrs
    nlist = index.nlist
    pos = st.rank_pos + jnp.arange(tries)  # (K,) the clusters a try would open
    opened = pos < nlist
    c = st.rank[jnp.clip(pos, 0, nlist - 1)]
    t = jnp.arange(pred.lo.shape[0])
    x = jnp.stack([pred.lo[t, chosen], pred.hi[t, chosen]])  # (2, T)
    bounds = run_position(
        ca.sorted_vals, chosen, ca.offsets[c][:, None], ca.offsets[c + 1][:, None],
        x[:, None, :], right=jnp.array([False, True])[:, None, None],
    )  # (2, K, T)
    beg, end = bounds[0], bounds[1]
    found = opened & (jnp.sum(jnp.maximum(end - beg, 0), axis=1) > 0)
    n_opened = jnp.sum(opened)
    hit = jnp.any(found)
    # the try that stops: the first non-empty cluster, else the last opened
    j = jnp.where(hit, jnp.argmax(found), n_opened - 1)
    rem = jnp.sum(jnp.maximum(st.term_end - st.term_beg, 0))
    need = (rem == 0) & ~st.b_exhausted
    move = need & (j >= 0)
    jc = jnp.maximum(j, 0)
    return st._replace(
        rank_pos=jnp.where(move, st.rank_pos + j + 1, st.rank_pos),
        term_beg=jnp.where(move, beg[jc], st.term_beg),
        term_end=jnp.where(move, end[jc], st.term_end),
        b_exhausted=st.b_exhausted | (need & ~hit & (n_opened < tries)),
    )


@staged("engine/bnext")
def step(index, q, pred, chosen, st: S.EngineState, pm, backend) -> S.EngineState:
    """One B.NEXT pull: fetch up to ``efi`` candidate records and VISIT them."""
    ca = index.cattrs
    T = pred.lo.shape[0]
    st = advance(index, pred, chosen, st, pm.cluster_tries)

    # fetch up to efi positions across terms (term-major order)
    rem = jnp.maximum(st.term_end - st.term_beg, 0)  # (T,)
    cum = jnp.cumsum(rem)
    total = cum[-1]
    cum_e = jnp.minimum(cum, pm.efi)
    taken = cum_e - jnp.concatenate([jnp.zeros((1,), cum.dtype), cum_e[:-1]])
    slots = jnp.arange(pm.efi)
    term_of = jnp.searchsorted(cum, slots, side="right").astype(jnp.int32)
    term_of_c = jnp.clip(term_of, 0, T - 1)
    before = jnp.where(term_of_c > 0, cum[jnp.maximum(term_of_c - 1, 0)], 0)
    pos = st.term_beg[term_of_c] + (slots - before)
    slot_ok = slots < jnp.minimum(total, pm.efi)
    attr_of = chosen[term_of_c]
    ids = ca.order[attr_of, jnp.clip(pos, 0, ca.n_records - 1)]
    # full-predicate filter on the remaining attributes (paper: linear scan)
    n = index.n_records
    safe = jnp.where(slot_ok, ids, n)
    passing = P.evaluate(pred, index.attrs[safe]) & slot_ok
    st = st._replace(term_beg=st.term_beg + taken)
    st = S.visit(index, q, pred, st, jnp.where(passing, ids, n), passing, pm, backend)
    return st._replace(stats=st.stats._replace(n_bcalls=st.stats.n_bcalls + 1))
