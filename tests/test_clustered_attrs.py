import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.clustered_attrs import (
    build_clustered_attrs,
    count_in_cluster,
    range_in_cluster,
    run_position,
)


@pytest.fixture(scope="module")
def ca_data():
    rng = np.random.default_rng(1)
    n, a, nlist = 3000, 3, 16
    attrs = rng.uniform(size=(n, a)).astype(np.float32)
    assign = rng.integers(0, nlist, n)
    return attrs, assign, build_clustered_attrs(attrs, assign, nlist)


def test_range_matches_bruteforce(ca_data):
    attrs, assign, ca = ca_data
    rng = np.random.default_rng(2)
    for _ in range(25):
        c = int(rng.integers(0, 16))
        a = int(rng.integers(0, 3))
        lo, hi = sorted(rng.uniform(0, 1, 2))
        beg, end = range_in_cluster(ca, c, a, lo, hi)
        got = set(np.asarray(ca.order[a])[int(beg) : int(end)].tolist())
        want = set(np.where((assign == c) & (attrs[:, a] >= lo) & (attrs[:, a] <= hi))[0].tolist())
        assert got == want


def test_empty_range(ca_data):
    _, _, ca = ca_data
    beg, end = range_in_cluster(ca, 0, 0, 0.5, 0.4)
    assert int(end - beg) <= 0 or int(end) == int(beg)


def test_count_matches_range(ca_data):
    attrs, assign, ca = ca_data
    cnt = int(count_in_cluster(ca, 3, 1, 0.25, 0.75))
    want = int(((assign == 3) & (attrs[:, 1] >= 0.25) & (attrs[:, 1] <= 0.75)).sum())
    assert cnt == want


@settings(max_examples=25, deadline=None)
@given(
    lo=st.floats(0, 1),
    hi=st.floats(0, 1),
    c=st.integers(0, 15),
    a=st.integers(0, 2),
)
def test_property_range_counts(ca_data, lo, hi, c, a):
    attrs, assign, ca = ca_data
    lo, hi = min(lo, hi), max(lo, hi)
    beg, end = range_in_cluster(ca, c, a, np.float32(lo), np.float32(hi))
    want = int(
        ((assign == c) & (attrs[:, a] >= np.float32(lo)) & (attrs[:, a] <= np.float32(hi))).sum()
    )
    assert int(end) - int(beg) == want


# ---------------------------------------------------------------------------
# run_position: the engine's probe, one element of the 2-D runs per halving,
# against np.searchsorted on each cluster's slice, both sides.
# ---------------------------------------------------------------------------


def _dup_layout(last_empty=False, one_run=False):
    """Values on a 0.1 grid (many duplicates); clusters 2 and 5 empty, and
    the last one too when ``last_empty``.  With ``one_run`` every row is in
    cluster 0, so a probe needs all ``N.bit_length()`` halvings."""
    rng = np.random.default_rng(3)
    n, a, nlist = 600, 3, 10
    attrs = (rng.integers(0, 11, size=(n, a)) / 10).astype(np.float32)
    live = [c for c in range(nlist) if c not in (2, 5) and not (last_empty and c == nlist - 1)]
    assign = np.zeros(n, np.int64) if one_run else rng.choice(live, n)
    return build_clustered_attrs(attrs, assign, nlist)


def _probe_values(vals, rng):
    """Every value present, the midpoints between them, and out-of-range."""
    u = np.unique(vals)
    return np.concatenate([u, (u[1:] + u[:-1]) / 2, rng.uniform(-0.5, 1.5, 8)]).astype(np.float32)


# clusters probed (None: all), the values probed, and the clusters the case
# needs empty
_RUN_CASES = {
    "duplicates": dict(clusters=None, xs="present", empty=()),
    "empty_clusters": dict(clusters=[2, 5], xs="mixed", empty=(2, 5)),
    "below_all": dict(clusters=None, xs=[-1.0, -1e30, -np.inf], empty=()),
    "above_all": dict(clusters=None, xs=[2.0, 1e30, np.inf], empty=()),
    "last_cluster": dict(clusters=[9], xs="mixed", empty=()),
    "empty_last_cluster": dict(clusters=[8, 9], xs="mixed", empty=(9,), last_empty=True),
    "one_run": dict(clusters=[0, 9], xs="mixed", empty=(9,), one_run=True),
}


@pytest.mark.parametrize("case", sorted(_RUN_CASES))
@pytest.mark.parametrize("right", [False, True])
def test_run_position_matches_searchsorted(case, right):
    spec = _RUN_CASES[case]
    ca = _dup_layout(spec.get("last_empty", False), spec.get("one_run", False))
    vals = np.asarray(ca.sorted_vals)
    offsets = np.asarray(ca.offsets)
    rng = np.random.default_rng(4)
    clusters = spec["clusters"] or range(ca.n_clusters)
    if spec["xs"] == "present":
        xs = np.unique(vals)
    elif spec["xs"] == "mixed":
        xs = _probe_values(vals, rng)
    else:
        xs = np.asarray(spec["xs"], np.float32)
    for c in spec["empty"]:
        assert offsets[c] == offsets[c + 1], c
    side = "right" if right else "left"
    for c in clusters:
        b, e = int(offsets[c]), int(offsets[c + 1])
        # all attributes and values of this cluster in one broadcast call
        got = np.asarray(run_position(ca.sorted_vals, np.arange(ca.n_attrs)[:, None],
                                      b, e, xs[None, :], right=right))
        want = np.stack([b + np.searchsorted(vals[a, b:e], xs, side=side)
                         for a in range(ca.n_attrs)])
        np.testing.assert_array_equal(got, want)
