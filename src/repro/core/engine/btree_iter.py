"""B.NEXT — the pull-based relational iterator (Algorithm 3).

Pulls predicate-passing records from the clustered B+-trees (per-attribute
sorted runs, see clustered_attrs.py) of the clusters nearest to the query,
on demand, through the ranked-cluster cursor stored in the engine state
(``rank`` / ``rank_pos`` / ``term_beg`` / ``term_end`` / ``b_exhausted``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs.profiling import staged

from .. import predicate as P
from ..clustered_attrs import searchsorted_slice
from . import state as S


@staged("engine/bnext")
def step(index, q, pred, chosen, st: S.EngineState, pm, backend) -> S.EngineState:
    """One B.NEXT pull: fetch up to ``efi`` candidate records and VISIT them."""
    ca = index.cattrs
    nlist = index.nlist
    T = pred.lo.shape[0]

    def advance_cluster(st: S.EngineState):
        """Advance the ranked-cluster cursor; point the per-term cursors at
        the new cluster's per-attribute sorted runs."""
        exhausted = st.rank_pos >= nlist
        c = st.rank[jnp.clip(st.rank_pos, 0, nlist - 1)]
        c_beg, c_end = ca.offsets[c], ca.offsets[c + 1]

        def one_term(t):
            a = chosen[t]
            lo_v, hi_v = pred.lo[t, a], pred.hi[t, a]
            beg = searchsorted_slice(ca.sorted_vals[a], c_beg, c_end, lo_v, "left")
            end = searchsorted_slice(ca.sorted_vals[a], c_beg, c_end, hi_v, "right")
            return beg, end

        beg, end = jax.vmap(one_term)(jnp.arange(T))
        return st._replace(
            rank_pos=jnp.where(exhausted, st.rank_pos, st.rank_pos + 1),
            term_beg=jnp.where(exhausted, st.term_beg, beg),
            term_end=jnp.where(exhausted, st.term_end, end),
            b_exhausted=st.b_exhausted | exhausted,
        )

    def maybe_advance(st: S.EngineState):
        rem = jnp.sum(jnp.maximum(st.term_end - st.term_beg, 0))
        need = (rem == 0) & ~st.b_exhausted
        return jax.lax.cond(need, advance_cluster, lambda s: s, st)

    st = jax.lax.fori_loop(0, pm.cluster_tries, lambda _, s: maybe_advance(s), st)

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
