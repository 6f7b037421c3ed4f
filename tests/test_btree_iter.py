"""B.NEXT's ranked-cluster advance (core/engine/btree_iter.advance).

The advance probes the next ``cluster_tries`` clusters at once through
single-element reads of the 2-D attribute runs and selects only the four
cursor leaves.  It must give exactly what the sequential form gives: up to
``cluster_tries`` tries of a ``lax.cond`` that opens the next cluster with
``searchsorted_slice`` over the lane's run ``sorted_vals[a]`` (kept below as
the reference).  That form, under ``vmap``, copies a whole run per (lane,
term) and broadcasts the runs to every lane; the HLO guard keeps those
shapes out of the compiled search.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compass import CompassParams, compass_search
from repro.core import predicate as P
from repro.core.clustered_attrs import searchsorted_slice
from repro.core.engine import btree_iter, driver
from repro.core.index import BuildConfig, build_index
from repro.data.synthetic import make_vector_corpus


def _sequential_advance(index, pred, chosen, st, tries):
    """The advance as one ``lax.cond`` per try over the whole state."""
    _sequential_advance.traced += 1
    ca = index.cattrs
    nlist = index.nlist
    T = pred.lo.shape[0]

    def advance_cluster(st):
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

    def maybe_advance(st):
        rem = jnp.sum(jnp.maximum(st.term_end - st.term_beg, 0))
        need = (rem == 0) & ~st.b_exhausted
        return jax.lax.cond(need, advance_cluster, lambda s: s, st)

    return jax.lax.fori_loop(0, tries, lambda _, s: maybe_advance(s), st)


_sequential_advance.traced = 0


@pytest.fixture(scope="module")
def small_index():
    """1,500 rows in 8 clusters, so that one B.NEXT pull can run through
    every ranked cluster at ``cluster_tries`` 8."""
    x, attrs, q = make_vector_corpus(1500, 16, 4, n_modes=16, seed=0)
    return build_index(x, attrs, BuildConfig(nlist=8)), q[:16]


def _preds(rng, n_queries, passrate, n_terms, disj):
    preds = []
    for _ in range(n_queries):
        terms = []
        for a in range(n_terms):
            lo = rng.uniform(0, 1 - passrate)
            terms.append(P.Pred.range(a, lo, lo + passrate))
        tree = P.Pred.or_(*terms) if disj else P.Pred.and_(*terms)
        preds.append(tree.tensor(4))
    return P.stack_predicates(preds)


# (index, predicate shape, params): the narrow conjunction over 8 clusters
# pulls B.NEXT until the ranking is exhausted; the 0.3%-wide range leaves
# most clusters without a row in range, so two tries often both come up
# empty with clusters still ranked after them
_CASES = {
    "conjunction": ("shared", dict(passrate=0.3, n_terms=2, disj=False), {}),
    "disjunction4": ("shared", dict(passrate=0.05, n_terms=4, disj=True), {}),
    "exhausts_clusters": ("small", dict(passrate=0.15, n_terms=4, disj=False),
                          dict(cluster_tries=8)),
    "tries_run_dry": ("shared", dict(passrate=0.003, n_terms=1, disj=False),
                      dict(cluster_tries=2)),
    "relational_ablation": ("shared", dict(passrate=0.3, n_terms=1, disj=False),
                            dict(use_graph=False)),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_advance_matches_sequential(case, built_index, corpus, small_index, monkeypatch):
    which, shape, extra = _CASES[case]
    if which == "shared":
        index, queries = built_index, corpus[2]
    else:
        index, queries = small_index
    pred = _preds(np.random.default_rng(41), len(queries), **shape)
    pm = CompassParams(k=10, ef=64, backend="ref", **extra)
    qj = jnp.asarray(queries)
    got = compass_search(index, qj, pred, pm)

    monkeypatch.setattr(btree_iter, "advance", _sequential_advance)
    before = _sequential_advance.traced
    # a fresh jit: the cached program of the call above holds the new advance
    want = jax.jit(lambda i, q, p: driver.compass_search_jit.__wrapped__(i, q, p, pm))(
        index, qj, pred)
    assert _sequential_advance.traced > before

    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    np.testing.assert_array_equal(np.asarray(got.dists), np.asarray(want.dists))
    for field in got.stats._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got.stats, field)),
                                      np.asarray(getattr(want.stats, field)), err_msg=field)
    assert np.asarray(got.stats.n_bcalls).min() > 0
    if case == "exhausts_clusters":
        assert np.all(np.asarray(got.stats.n_clusters_ranked) == index.nlist)


def test_no_per_lane_attribute_runs_in_hlo(small_index):
    """The compiled search holds no per-lane copy of an attribute run: not
    the runs broadcast to every lane (``f32[B,A,N]``) nor a gathered run
    per (lane, term) (``f32[B*T,1,N]``, ``f32[B*T,1,1,N]``), the shapes the
    chip showed.  The planner is off: its exact probe
    (``planner/stats.term_run_bounds``) still gathers a run per lane."""
    index, _ = small_index
    n_rows, N = index.n_records, 4096
    B, T, A = 8, 4, index.cattrs.n_attrs

    def grown(leaf):
        grow = {n_rows: N, n_rows + 1: N + 1}
        return jax.ShapeDtypeStruct(tuple(grow.get(d, d) for d in leaf.shape), leaf.dtype)

    spec = jax.ShapeDtypeStruct((B, T, A), jnp.float32)
    pm = CompassParams(k=10, ef=64, backend="ref")
    text = driver.compass_search_jit.lower(
        jax.tree.map(grown, index), jax.ShapeDtypeStruct((B, 16), jnp.float32),
        P.Predicate(spec, spec), pm=pm,
    ).compile().as_text()
    shapes = set(re.findall(r"\b[a-z]+\d*\[[\d,]*\]", text))
    assert f"f32[{A},{N}]" in shapes  # the runs themselves, unbatched
    for bad in (f"f32[{B},{A},{N}]", f"f32[{B * T},1,{N}]", f"f32[{B * T},1,1,{N}]"):
        assert bad not in shapes, bad
