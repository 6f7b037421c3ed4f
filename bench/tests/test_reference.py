"""The benchmark's reference against the program's own brute force: two
independent implementations of the same filtered top-k semantics."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data, reference


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(5)
    x = rng.normal(size=(3000, 32)).astype(np.float32) * 3
    attrs = rng.uniform(size=(3000, 4)).astype(np.float32)
    q = rng.normal(size=(16, 32)).astype(np.float32) * 3
    mix = [{"weight": 1, "terms": 1, "attrs_per_term": 2, "width": 0.3},
           {"weight": 1, "terms": 4, "attrs_per_term": 1, "width": 0.05,
            "distinct_attrs": True}]
    preds = data.make_predicates(rng, 16, mix, 4)
    return x, attrs, q, preds


def test_predicate_semantics_match_the_program(corpus):
    from repro.core import predicate as P

    x, attrs, q, preds = corpus
    for i in range(len(preds)):
        lo, hi = preds[i]
        want = np.asarray(P.evaluate(P.Predicate(jnp.asarray(lo), jnp.asarray(hi)),
                                     jnp.asarray(attrs)))
        got = np.asarray(reference.passes(jnp.asarray(attrs), lo, hi))
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("precision", ["highest", "high"])
def test_top_k_matches_core_brute_force(corpus, precision):
    from repro.core import predicate as P
    from repro.core.baselines import brute_force

    x, attrs, q, preds = corpus
    for t in sorted({preds[i][0].shape[0] for i in range(len(preds))}):
        sel = [i for i in range(len(preds)) if preds[i][0].shape[0] == t]
        lo = np.stack([preds[i][0] for i in sel])
        hi = np.stack([preds[i][1] for i in sel])
        want = brute_force(jnp.asarray(x), jnp.asarray(attrs), jnp.asarray(q[sel]),
                           P.Predicate(jnp.asarray(lo), jnp.asarray(hi)), 10)
        ids, dists = reference.scan(jnp.asarray(x), jnp.asarray(attrs), jnp.asarray(q[sel]),
                                    jnp.asarray(lo), jnp.asarray(hi), depth=10, metric="l2",
                                    precision=precision)
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(want.ids))
        np.testing.assert_allclose(np.asarray(dists), np.asarray(want.dists), rtol=1e-4)
        for j, i in enumerate(sel):
            t_ids, t_d = reference.truth(x, np.asarray(ids)[j], q[i], 10, "l2")
            np.testing.assert_array_equal(t_ids, np.asarray(want.ids)[j])
            np.testing.assert_allclose(t_d, ((x[t_ids].astype(np.float64) - q[i]) ** 2).sum(1))


def test_high_precision_is_below_float32():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(8, 128)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32))
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64).T
    err_f32 = np.abs(np.asarray(reference.matmul(a, b, "highest")) - exact).max()
    err_high = np.abs(np.asarray(reference.matmul(a, b, "high")) - exact).max()
    assert err_high > 3 * err_f32
    with pytest.raises(ValueError):
        reference.matmul(a, b, "default")
