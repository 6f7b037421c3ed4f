import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import predicate as P


def test_simple_range():
    p = P.Pred.range(0, 0.2, 0.5).tensor(n_attrs=2)
    attrs = jnp.asarray([[0.3, 9.0], [0.1, 0.0], [0.5, -1.0], [0.51, 0.0]])
    out = np.asarray(P.evaluate(p, attrs))
    assert out.tolist() == [True, False, True, False]


def test_conjunction_and_disjunction():
    conj = P.Pred.and_(P.Pred.range(0, 0.0, 0.5), P.Pred.ge(1, 0.5)).tensor(2)
    disj = P.Pred.or_(P.Pred.range(0, 0.0, 0.5), P.Pred.ge(1, 0.5)).tensor(2)
    attrs = jnp.asarray([[0.2, 0.9], [0.2, 0.1], [0.9, 0.9], [0.9, 0.1]])
    assert np.asarray(P.evaluate(conj, attrs)).tolist() == [True, False, False, False]
    assert np.asarray(P.evaluate(disj, attrs)).tolist() == [True, True, True, False]


def test_nested_tree_dnf_equals_python_eval():
    # ((a0 in [.1,.4] AND a1 >= .5) OR a2 <= .2) AND a3 in [.3,.9]
    tree = P.Pred.and_(
        P.Pred.or_(
            P.Pred.and_(P.Pred.range(0, 0.1, 0.4), P.Pred.ge(1, 0.5)),
            P.Pred.le(2, 0.2),
        ),
        P.Pred.range(3, 0.3, 0.9),
    )
    pred = tree.tensor(4)
    rng = np.random.default_rng(0)
    attrs = rng.uniform(size=(500, 4)).astype(np.float32)
    got = np.asarray(P.evaluate(pred, jnp.asarray(attrs)))
    want = (
        ((attrs[:, 0] >= 0.1) & (attrs[:, 0] <= 0.4) & (attrs[:, 1] >= 0.5))
        | (attrs[:, 2] <= 0.2)
    ) & ((attrs[:, 3] >= 0.3) & (attrs[:, 3] <= 0.9))
    np.testing.assert_array_equal(got, want)


def test_equality_predicate():
    p = P.Pred.eq(1, 3.0).tensor(2)
    attrs = jnp.asarray([[0.0, 3.0], [0.0, 2.999]])
    assert np.asarray(P.evaluate(p, attrs)).tolist() == [True, False]


def test_stack_predicates_pads_unsatisfiable():
    p1 = P.Pred.range(0, 0.0, 1.0).tensor(2)  # T=1
    p2 = P.Pred.or_(P.Pred.le(0, 0.1), P.Pred.ge(1, 0.9)).tensor(2)  # T=2
    batched = P.stack_predicates([p1, p2])
    assert batched.lo.shape == (2, 2, 2)
    attrs = jnp.asarray([[0.5, 0.5]])
    # query 0: in range -> True; pad term must not fire
    out0 = P.evaluate(P.Predicate(batched.lo[0], batched.hi[0]), attrs)
    assert bool(out0[0])
    out1 = P.evaluate(P.Predicate(batched.lo[1], batched.hi[1]), attrs)
    assert not bool(out1[0])


def test_empty_dnf_is_unsatisfiable():
    # contradictory conjunction: every DNF term drops -> empty -> tensor()
    # must lower to an unsatisfiable predicate, not an empty array
    tree = P.Pred.and_(P.Pred.le(0, 0.2), P.Pred.ge(0, 0.8))
    assert tree.to_dnf() == []
    pred = tree.tensor(2)
    assert pred.lo.shape == (1, 2)
    attrs = jnp.asarray([[0.0, 0.0], [0.5, 0.5], [1.0, 1.0]])
    assert not np.asarray(P.evaluate(pred, attrs)).any()


def test_never_true_rejects_everything():
    pred = P.never_true(3, n_terms=2)
    attrs = jnp.asarray([[0.0, 0.5, 1.0], [P.NEG_INF, 0.0, P.POS_INF]])
    assert not np.asarray(P.evaluate(pred, attrs)).any()


def test_term_bucket_powers_of_two():
    assert [P.term_bucket(t) for t in (1, 2, 3, 4, 5, 8, 9)] == [1, 2, 4, 4, 8, 8, 16]
    with pytest.raises(ValueError):
        P.term_bucket(0)


def test_pad_terms_preserves_evaluation():
    tree = P.Pred.or_(P.Pred.le(0, 0.3), P.Pred.ge(1, 0.7))  # T=2
    base = tree.tensor(2)
    rng = np.random.default_rng(3)
    attrs = jnp.asarray(rng.uniform(size=(64, 2)).astype(np.float32))
    want = np.asarray(P.evaluate(base, attrs))
    for T in (2, 4, 8):
        padded = P.pad_terms(base, T)
        assert padded.lo.shape == (T, 2)
        np.testing.assert_array_equal(np.asarray(P.evaluate(padded, attrs)), want)
    with pytest.raises(ValueError, match="terms"):
        P.pad_terms(base, 1)


def test_stack_predicates_to_requested_bucket():
    p1 = P.Pred.range(0, 0.0, 1.0).tensor(2)  # T=1
    p2 = P.Pred.or_(P.Pred.le(0, 0.1), P.Pred.ge(1, 0.9)).tensor(2)  # T=2
    batched = P.stack_predicates([p1, p2], n_terms=4)
    assert batched.lo.shape == (2, 4, 2)
    attrs = jnp.asarray([[0.5, 0.5]])
    assert bool(P.evaluate(P.Predicate(batched.lo[0], batched.hi[0]), attrs)[0])
    assert not bool(P.evaluate(P.Predicate(batched.lo[1], batched.hi[1]), attrs)[0])
    with pytest.raises(ValueError, match="terms"):
        P.stack_predicates([p1, p2], n_terms=1)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.floats(0, 1), min_size=4, max_size=4), st.data())
def test_property_dnf_matches_tree_semantics(attr_vals, data):
    """Random small predicate trees: DNF tensor evaluation == direct eval."""

    def gen_tree(depth):
        if depth == 0 or data.draw(st.booleans()):
            a = data.draw(st.integers(0, 3))
            lo = data.draw(st.floats(0, 1))
            hi = data.draw(st.floats(0, 1))
            return P.Pred.range(a, min(lo, hi), max(lo, hi))
        kids = [gen_tree(depth - 1) for _ in range(data.draw(st.integers(2, 3)))]
        return P.Pred.and_(*kids) if data.draw(st.booleans()) else P.Pred.or_(*kids)

    def eval_tree(t, vals):
        if t.kind == "leaf":
            return t.lo <= vals[t.attr] <= t.hi
        if t.kind == "and":
            return all(eval_tree(c, vals) for c in t.children)
        return any(eval_tree(c, vals) for c in t.children)

    tree = gen_tree(2)
    pred = tree.tensor(4)
    got = bool(P.evaluate(pred, jnp.asarray([attr_vals], jnp.float32))[0])
    want = eval_tree(tree, [np.float32(v) for v in attr_vals])
    assert got == want
