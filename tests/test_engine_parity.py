"""Pinned engine results: a fixed seeded corpus searched in every engine
mode must give the ids, integer ``SearchStats`` and distances recorded in
``tests/data/engine_parity.json``.

The engine's internal storage (the visited set, the queues) can change
only if these stay put: ``ids`` and every integer stats field exactly,
``dists`` to 1e-6.  The file is written by this module::

    PYTHONPATH=src JAX_PLATFORMS=cpu python tests/test_engine_parity.py

Rewrite it only for a change that is meant to move results, and say so.
"""
from __future__ import annotations

import json
import math
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import predicate as P
from repro.core.planner import plan as QP
from repro.core.quant import QuantParams
from repro.compass import CompassParams, compass_search

PINNED = pathlib.Path(__file__).parent / "data" / "engine_parity.json"
N, D, A, B = 1500, 16, 4, 8
# one passrate per query: G.NEXT's one-hop, two-hop and pivot branches and
# B.NEXT's injection all fire somewhere in the batch
MIXED = (0.005, 0.02, 0.05, 0.1, 0.2, 0.4, 0.7, 1.0)
FLOAT_STATS = {"est_sel"}

# name -> (params, per-query passrates, planner mode every query must take)
CASES = {
    "cooperative": (CompassParams(k=10, ef=32, backend="ref"), MIXED, None),
    "prefilter": (CompassParams(k=10, ef=32, backend="ref", planner=True),
                  (0.01,) * B, QP.PREFILTER),
    "postfilter": (CompassParams(k=10, ef=32, backend="ref", planner=True),
                   (1.0,) * B, QP.POSTFILTER),
    "in_filter": (CompassParams(k=10, ef=32, backend="ref", in_filter=True,
                                use_btree=False), MIXED, None),
    "no_btree": (CompassParams(k=10, ef=32, backend="ref", use_btree=False), MIXED, None),
    "no_graph": (CompassParams(k=10, ef=32, backend="ref", use_graph=False), MIXED, None),
    "quant": (CompassParams(k=10, ef=32, backend="ref",
                            quant=QuantParams(refine_factor=2)), MIXED, None),
}
TERMS = (1, 4)


def _corpus():
    rng = np.random.default_rng(17)
    centers = rng.normal(size=(24, D)).astype(np.float32) * 3
    x = (centers[rng.integers(0, 24, N)] + rng.normal(size=(N, D))).astype(np.float32)
    attrs = rng.uniform(size=(N, A)).astype(np.float32)
    queries = (centers[rng.integers(0, 24, B)] + rng.normal(size=(B, D))).astype(np.float32)
    return x, attrs, queries


def _predicates(terms: int, passrates) -> P.Predicate:
    """A conjunction over two attributes (one term) or a disjunction of
    four single-attribute ranges (four terms) that passes about
    ``passrate`` of the uniform attributes."""
    rng = np.random.default_rng(terms)
    preds = []
    for p in passrates:
        if terms == 1:
            w = math.sqrt(p)
            lo = rng.uniform(0, 1 - w, size=2)
            tree = P.Pred.and_(*(P.Pred.range(a, lo[a], lo[a] + w) for a in range(2)))
        else:
            w = 1 - (1 - p) ** 0.25
            lo = rng.uniform(0, 1 - w, size=4)
            tree = P.Pred.or_(*(P.Pred.range(a, lo[a], lo[a] + w) for a in range(4)))
        preds.append(tree.tensor(A))
    return P.stack_predicates(preds)


def build():
    """(index, quantized index, queries) of the pinned corpus."""
    from repro.core.index import BuildConfig, build_index
    from repro.core.quant import QuantConfig, quantize_index

    x, attrs, queries = _corpus()
    index = build_index(x, attrs, BuildConfig(m=8, nlist=16))
    return index, quantize_index(index, QuantConfig(m=4, iters=4), "l2"), jnp.asarray(queries)


def run_case(built, name: str, terms: int) -> dict:
    index, qindex, queries = built
    pm, passrates, _ = CASES[name]
    if pm.quant is not None:
        index = qindex
    res = compass_search(index, queries, _predicates(terms, passrates), pm)
    out = {"ids": np.asarray(res.ids).tolist(), "dists": np.asarray(res.dists).tolist()}
    for field, v in res.stats._asdict().items():
        out[field] = np.asarray(v).tolist()
    return out


@pytest.fixture(scope="module")
def built():
    return build()


@pytest.fixture(scope="module")
def pinned():
    return json.loads(PINNED.read_text())


@pytest.mark.parametrize("terms", TERMS)
@pytest.mark.parametrize("name", list(CASES))
def test_engine_results_pinned(built, pinned, name, terms):
    got = run_case(built, name, terms)
    want = pinned[f"{name}/T{terms}"]
    mode = CASES[name][2]
    if mode is not None:
        assert got["mode"] == [mode] * B
    assert got["ids"] == want["ids"]
    for field, v in want.items():
        if field in ("ids", "dists") or field in FLOAT_STATS:
            continue
        assert got[field] == v, field
    np.testing.assert_allclose(np.asarray(got["dists"], np.float64),
                               np.asarray(want["dists"], np.float64), rtol=1e-6, atol=0)
    for field in FLOAT_STATS:
        np.testing.assert_allclose(got[field], want[field], rtol=1e-6, atol=0)


if __name__ == "__main__":
    b = build()
    table = {f"{name}/T{terms}": run_case(b, name, terms) for name in CASES for terms in TERMS}
    PINNED.parent.mkdir(exist_ok=True)
    PINNED.write_text(json.dumps(table, indent=None, separators=(",", ":")) + "\n")
    print(f"wrote {len(table)} cases to {PINNED}")
