import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.clustered_attrs import (
    build_clustered_attrs,
    count_in_cluster,
    range_in_cluster,
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
