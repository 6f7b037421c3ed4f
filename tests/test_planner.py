"""Cost-based planner: estimator accuracy/monotonicity (property tests),
exact run probes, PREFILTER exactness + parity with COOPERATIVE, per-mode
dispatch, and the trustworthy-stats fixes (n_cdist / n_clusters_ranked)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import predicate as P
from repro.core.baselines import brute_force, recall
from repro.core.clustered_attrs import build_clustered_attrs
from repro.core.planner import estimate as E
from repro.core.planner import plan as QP
from repro.core.planner.stats import build_attr_stats, term_run_bounds
from repro.compass import CompassParams, compass_search


@pytest.fixture(scope="module")
def stats_data():
    rng = np.random.default_rng(11)
    n, a, nlist = 4000, 3, 16
    attrs = rng.uniform(size=(n, a)).astype(np.float32)
    assign = rng.integers(0, nlist, n)
    ca = build_clustered_attrs(attrs, assign, nlist)
    astats = build_attr_stats(attrs, assign, nlist)
    return attrs, assign, ca, astats


def _pred(n_attrs, bounds):  # bounds: {attr: (lo, hi)}
    lo = np.full((1, n_attrs), P.NEG_INF, np.float32)
    hi = np.full((1, n_attrs), P.POS_INF, np.float32)
    for a, (l, h) in bounds.items():
        lo[0, a], hi[0, a] = l, h
    return jnp.asarray(lo), jnp.asarray(hi)


def _exact_passrate(attrs, lo, hi):
    lo, hi = np.asarray(lo), np.asarray(hi)
    term_ok = np.all((attrs[:, None, :] >= lo) & (attrs[:, None, :] <= hi), axis=-1)
    return np.any(term_ok, axis=-1).mean()


# -- stats ------------------------------------------------------------------


def test_index_carries_attr_stats(built_index):
    s = built_index.astats
    assert s is not None
    nlist, a = built_index.nlist, built_index.n_attrs
    assert s.edges.shape == (a, 65)
    assert s.cluster_edges.shape == (nlist, a, 9)
    assert np.all(np.diff(np.asarray(s.edges), axis=-1) >= 0)
    assert float(np.sum(np.asarray(s.cluster_counts))) == built_index.n_records


def test_exact_run_probes_match_numpy(stats_data):
    attrs, assign, ca, _ = stats_data
    rng = np.random.default_rng(3)
    for _ in range(10):
        a = int(rng.integers(0, attrs.shape[1]))
        lo, hi = sorted(rng.uniform(0, 1, 2))
        plo, phi = _pred(attrs.shape[1], {a: (lo, hi)})
        chosen = P.chosen_attrs(P.Predicate(plo, phi))
        beg, end = term_run_bounds(ca, plo, phi, chosen)
        got = int(np.sum(np.maximum(np.asarray(end) - np.asarray(beg), 0)))
        want = int(
            ((attrs[:, a] >= np.float32(lo)) & (attrs[:, a] <= np.float32(hi))).sum()
        )
        assert got == want
        # per-cluster counts too, not just the total
        per_c = np.asarray(end - beg)[0]
        for c in range(ca.n_clusters):
            wc = int(
                (
                    (assign == c)
                    & (attrs[:, a] >= np.float32(lo))
                    & (attrs[:, a] <= np.float32(hi))
                ).sum()
            )
            assert per_c[c] == wc


# -- estimator (property tests) ---------------------------------------------


@settings(max_examples=30, deadline=None)
@given(
    lo0=st.floats(0, 1),
    w0=st.floats(0, 1),
    lo1=st.floats(0, 1),
    w1=st.floats(0, 1),
)
def test_estimate_close_to_exact(stats_data, lo0, w0, lo1, w1):
    """Estimated selectivity within epsilon of the exact pass rate on
    synthetic (uniform, independent) attrs — conjunction of two ranges."""
    attrs, _, _, astats = stats_data
    plo, phi = _pred(
        attrs.shape[1], {0: (lo0, min(lo0 + w0, 1.0)), 1: (lo1, min(lo1 + w1, 1.0))}
    )
    _, est = E.estimate_matches(astats, plo, phi)
    exact = _exact_passrate(attrs, plo, phi)
    assert abs(float(est) - exact) <= 0.06


@settings(max_examples=30, deadline=None)
@given(
    lo=st.floats(0, 1),
    w=st.floats(0, 0.8),
    dlo=st.floats(0, 0.3),
    dhi=st.floats(0, 0.3),
    attr=st.integers(0, 2),
)
def test_estimate_monotone_under_widening(stats_data, lo, w, dlo, dhi, attr):
    attrs, _, _, astats = stats_data
    hi = min(lo + w, 1.0)
    plo, phi = _pred(attrs.shape[1], {attr: (lo, hi)})
    wlo, whi = _pred(attrs.shape[1], {attr: (lo - dlo, hi + dhi)})
    _, est = E.estimate_matches(astats, plo, phi)
    _, est_wide = E.estimate_matches(astats, wlo, whi)
    assert float(est_wide) >= float(est) - 1e-6
    # the global-histogram path must be monotone too
    g = float(E.estimate_selectivity_global(astats, plo, phi))
    g_wide = float(E.estimate_selectivity_global(astats, wlo, whi))
    assert g_wide >= g - 1e-6


def test_estimate_handles_padding_and_vacuous(stats_data):
    attrs, _, _, astats = stats_data
    a = attrs.shape[1]
    # unsatisfiable pad term contributes nothing
    pad = P.pad_terms(P.Pred.range(0, 0.2, 0.4).tensor(a), 4)
    nat = P.Pred.range(0, 0.2, 0.4).tensor(a)
    _, est_pad = E.estimate_matches(astats, pad.lo, pad.hi)
    _, est_nat = E.estimate_matches(astats, nat.lo, nat.hi)
    assert float(est_pad) == pytest.approx(float(est_nat), abs=1e-6)
    # vacuous predicate estimates ~1, never_true estimates ~0
    true_p = P.always_true(a)
    _, est_true = E.estimate_matches(astats, true_p.lo, true_p.hi)
    assert float(est_true) >= 0.99
    false_p = P.never_true(a)
    _, est_false = E.estimate_matches(astats, false_p.lo, false_p.hi)
    assert float(est_false) <= 1e-6


# -- mode selection + execution ---------------------------------------------


def _preds(rng, n_queries, n_attrs, passrate, n_terms, disj=False):
    preds = []
    for _ in range(n_queries):
        terms = []
        for a in range(n_terms):
            lo = rng.uniform(0, 1 - passrate)
            terms.append(P.Pred.range(a, lo, lo + passrate))
        tree = P.Pred.or_(*terms) if disj else P.Pred.and_(*terms)
        preds.append(tree.tensor(n_attrs))
    return P.stack_predicates(preds)


def test_high_selectivity_chooses_prefilter_and_is_exact(built_index, corpus):
    """Acceptance: pass rate ~1% -> PREFILTER, bitwise equal to a
    brute-force filtered scan.

    The reference scan materializes *every* passing record (found
    independently in numpy) and scores it through the engine's own
    ``scan_scores`` at the engine's shape, so the comparison pins down the
    planner's materialization / dedup / top-k merge exactly: ids are
    asserted bitwise.  Distances are asserted to ~1 f32 ULP: XLA fuses the
    row reduction differently inside the jitted search than in a
    standalone call, so bit-for-bit float equality only holds *within* one
    compiled program (the ref-vs-pallas parity test covers that); across
    programs the same caveat as ivf_score applies (engine/backend.py).
    """
    from repro.core.engine import resolve_backend

    x, attrs, queries = corpus
    rng = np.random.default_rng(21)
    pred = _preds(rng, 16, 4, 0.01, 1)
    qj = jnp.asarray(queries)
    pm = CompassParams(k=10, ef=64, planner=True, backend="ref")
    res = compass_search(built_index, qj, pred, pm)
    assert np.all(np.asarray(res.stats.mode) == QP.PREFILTER)
    ids = np.asarray(res.ids)
    dists = np.asarray(res.dists)
    n = x.shape[0]
    cap = pm.resolved().prefilter_cap
    lo, hi = np.asarray(pred.lo), np.asarray(pred.hi)

    # brute-force filtered scan: all passing ids, engine scoring, top-k
    passing_sets = [
        np.where(
            np.any(np.all((attrs[:, None, :] >= lo[b]) & (attrs[:, None, :] <= hi[b]), -1), -1)
        )[0]
        for b in range(ids.shape[0])
    ]
    assert max(len(p) for p in passing_sets) <= cap  # fully materializable
    scan_ids = np.full((ids.shape[0], cap), n, np.int32)
    scan_mask = np.zeros((ids.shape[0], cap), bool)
    for b, p in enumerate(passing_sets):
        scan_ids[b, : len(p)] = p
        scan_mask[b, : len(p)] = True
    d_scan, p_scan = resolve_backend("ref").scan_scores(
        built_index, qj, P.Predicate(pred.lo, pred.hi),
        jnp.asarray(scan_ids), jnp.asarray(scan_mask), "l2",
    )
    d_scan = np.asarray(jnp.where(p_scan, d_scan, jnp.inf))

    xj = jnp.asarray(x)
    for b, p in enumerate(passing_sets):
        order = np.argsort(d_scan[b], kind="stable")[:10]
        k_real = min(len(p), 10)
        want_ids = scan_ids[b][order][:k_real]
        np.testing.assert_array_equal(ids[b, :k_real], want_ids)
        np.testing.assert_allclose(
            dists[b, :k_real], d_scan[b][order][:k_real], rtol=1e-6
        )
        assert np.all(ids[b, k_real:] == n)  # unfilled slots are sentinels
        assert np.all(~np.isfinite(dists[b, k_real:]))
        # independent recompute anchors the scoring itself (ULP tolerance)
        d_ind = np.asarray(jnp.sum((xj[ids[b, :k_real]] - qj[b]) ** 2, axis=-1))
        np.testing.assert_allclose(dists[b, :k_real], d_ind, rtol=1e-5)


def test_prefilter_matches_cooperative_topk(built_index, corpus):
    """Recall parity: on fully-materializable predicates PREFILTER and
    forced-COOPERATIVE return identical top-k."""
    x, attrs, queries = corpus
    rng = np.random.default_rng(22)
    pred = _preds(rng, 16, 4, 0.008, 1)  # ~48 matches of 6000, < ef
    qj = jnp.asarray(queries)
    on = compass_search(built_index, qj, pred, CompassParams(k=10, ef=64, planner=True))
    off = compass_search(built_index, qj, pred, CompassParams(k=10, ef=64, planner=False))
    assert np.all(np.asarray(on.stats.mode) == QP.PREFILTER)
    assert np.all(np.asarray(off.stats.mode) == QP.COOPERATIVE)
    np.testing.assert_array_equal(np.asarray(on.ids), np.asarray(off.ids))
    np.testing.assert_array_equal(np.asarray(on.dists), np.asarray(off.dists))


def test_postfilter_mode_on_vacuous_filters(built_index, corpus):
    x, attrs, queries = corpus
    rng = np.random.default_rng(23)
    pred = _preds(rng, 16, 4, 1.0, 1)
    qj = jnp.asarray(queries)
    res = compass_search(built_index, qj, pred, CompassParams(k=10, ef=128, planner=True))
    assert np.all(np.asarray(res.stats.mode) == QP.POSTFILTER)
    assert np.all(np.asarray(res.stats.n_bcalls) == 0)  # B.NEXT disabled
    truth = brute_force(jnp.asarray(x), jnp.asarray(attrs), qj, pred, 10)
    r = recall(np.asarray(res.ids), np.asarray(truth.ids), np.asarray(truth.dists), x.shape[0])
    assert r >= 0.85, r


def test_moderate_selectivity_stays_cooperative(built_index, corpus):
    x, attrs, queries = corpus
    rng = np.random.default_rng(24)
    pred = _preds(rng, 16, 4, 0.3, 2)
    res = compass_search(
        built_index, jnp.asarray(queries), pred, CompassParams(k=10, ef=64, planner=True)
    )
    assert np.all(np.asarray(res.stats.mode) == QP.COOPERATIVE)
    truth = brute_force(jnp.asarray(x), jnp.asarray(attrs), jnp.asarray(queries), pred, 10)
    r = recall(np.asarray(res.ids), np.asarray(truth.ids), np.asarray(truth.dists), x.shape[0])
    assert r >= 0.9, r


@pytest.mark.parametrize(
    "case",
    ["prefilter_regime", "cooperative_regime", "postfilter_regime", "disjunction"],
)
def test_planner_backend_parity(built_index, corpus, case):
    """ref and pallas backends stay bitwise-identical with the planner on
    (the batched run scan included)."""
    kw = {
        "prefilter_regime": dict(passrate=0.01, n_terms=1),
        "cooperative_regime": dict(passrate=0.3, n_terms=2),
        "postfilter_regime": dict(passrate=1.0, n_terms=1),
        "disjunction": dict(passrate=0.02, n_terms=3, disj=True),
    }[case]
    x, attrs, queries = corpus
    rng = np.random.default_rng(25)
    pred = _preds(rng, 16, 4, **kw)
    qj = jnp.asarray(queries)
    ref = compass_search(built_index, qj, pred, CompassParams(k=10, ef=64, planner=True, backend="ref"))
    pal = compass_search(built_index, qj, pred, CompassParams(k=10, ef=64, planner=True, backend="pallas"))
    np.testing.assert_array_equal(np.asarray(ref.stats.mode), np.asarray(pal.stats.mode))
    np.testing.assert_array_equal(np.asarray(ref.ids), np.asarray(pal.ids))
    np.testing.assert_array_equal(np.asarray(ref.dists), np.asarray(pal.dists))


def test_planner_off_by_default_and_flag_respected(built_index, corpus):
    assert CompassParams().planner is False
    x, attrs, queries = corpus
    rng = np.random.default_rng(26)
    pred = _preds(rng, 16, 4, 0.01, 1)  # would be PREFILTER if planner ran
    res = compass_search(built_index, jnp.asarray(queries), pred, CompassParams(k=10, ef=64))
    assert np.all(np.asarray(res.stats.mode) == QP.COOPERATIVE)


def test_planner_requires_attr_stats(built_index, corpus):
    x, attrs, queries = corpus
    legacy = built_index._replace(astats=None)  # pre-planner index
    rng = np.random.default_rng(27)
    pred = _preds(rng, 4, 4, 0.3, 1)
    with pytest.raises(ValueError, match="attribute statistics"):
        compass_search(
            legacy, jnp.asarray(queries[:4]), pred, CompassParams(k=10, ef=64, planner=True)
        )


def test_disjunction_prefilter_dedups_across_terms(built_index, corpus):
    """A record matching several OR terms must appear once in the top-k."""
    x, attrs, queries = corpus
    # two overlapping ranges on the same attribute -> every match sits in
    # both terms' runs
    tree = P.Pred.or_(P.Pred.range(0, 0.10, 0.13), P.Pred.range(0, 0.10, 0.13))
    pred = P.stack_predicates([tree.tensor(4) for _ in range(8)])
    res = compass_search(
        built_index, jnp.asarray(queries[:8]), pred, CompassParams(k=10, ef=64, planner=True)
    )
    assert np.all(np.asarray(res.stats.mode) == QP.PREFILTER)
    ids = np.asarray(res.ids)
    n = x.shape[0]
    for b in range(ids.shape[0]):
        real = ids[b][ids[b] < n]
        assert len(set(real.tolist())) == len(real)


# -- trustworthy stats (satellite fix) --------------------------------------


def test_ncdist_reports_true_count(built_index, corpus):
    """n_cdist was hardcoded to nlist even when the centroid ranking had no
    consumer; it must now report the true count."""
    x, attrs, queries = corpus
    rng = np.random.default_rng(28)
    pred = _preds(rng, 16, 4, 0.3, 1)
    qj = jnp.asarray(queries)
    nlist = built_index.nlist
    res = compass_search(built_index, qj, pred, CompassParams(k=10, ef=64))
    assert np.all(np.asarray(res.stats.n_cdist) == nlist)  # ranking consumed
    # pure-graph ablation with non-adaptive entry: ranking never consumed
    pm_off = CompassParams(k=10, ef=64, use_btree=False, adaptive_entry=False)
    res_off = compass_search(built_index, qj, pred, pm_off)
    assert np.all(np.asarray(res_off.stats.n_cdist) == 0)
    # adaptive entry alone still consumes the full ranking
    pm_entry = CompassParams(k=10, ef=64, use_btree=False, adaptive_entry=True)
    res_entry = compass_search(built_index, qj, pred, pm_entry)
    assert np.all(np.asarray(res_entry.stats.n_cdist) == nlist)


def test_n_clusters_ranked_tracks_bnext(built_index, corpus):
    x, attrs, queries = corpus
    qj = jnp.asarray(queries)
    rng = np.random.default_rng(29)
    # low passrate forces relational injection -> clusters actually opened
    pred = _preds(rng, 16, 4, 0.3, 4)
    res = compass_search(built_index, qj, pred, CompassParams(k=10, ef=64))
    ranked = np.asarray(res.stats.n_clusters_ranked)
    assert np.all(ranked <= built_index.nlist)
    assert ranked.mean() > 0
    # btree disabled -> nothing is ever opened
    res_nb = compass_search(built_index, qj, pred, CompassParams(k=10, ef=64, use_btree=False))
    assert np.all(np.asarray(res_nb.stats.n_clusters_ranked) == 0)
