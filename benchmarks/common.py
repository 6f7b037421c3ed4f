"""Shared benchmark infrastructure: datasets, workloads, method runners.

Scale note: the paper's corpora are ~1-2M vectors x 100-1024 dims on a Xeon
with SIMD; this container is a single CPU core running batched JAX, so the
default benchmark corpus is 60k x 48d with the same *structure* (clustered
modes + 4 uniform attributes, paper §V.A).  All comparisons are relative
and the primary hardware-independent metric is #Comp (vector distance
computations), exactly as the paper argues.  Set REPRO_BENCH_N/REPRO_BENCH_D
to rescale.

Indices are built from the dataset seed once per process.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import predicate as P
from repro.core.baselines import brute_force, navix_search, postfilter_search, prefilter_search, recall
from repro.core.index import BuildConfig, build_index
from repro.compass import CompassParams, compass_search
from repro.data.synthetic import make_vector_corpus

N = int(os.environ.get("REPRO_BENCH_N", 60000))
D = int(os.environ.get("REPRO_BENCH_D", 48))
N_ATTRS = 4
N_QUERIES = int(os.environ.get("REPRO_BENCH_Q", 64))
K = 10
# scoring backend for the compass runs: "ref" | "pallas" | "auto"
BACKEND = os.environ.get("REPRO_BENCH_BACKEND", "auto")


def bench_metadata() -> dict:
    """Provenance block written into every BENCH_*.json: which engine and
    backend produced the numbers, on what platform/scale — so benchmark
    trajectories across PRs stay attributable."""
    from repro.compass import ENGINE_VERSION
    from repro.core.engine import resolve_backend

    return {
        "engine_version": ENGINE_VERSION,
        "backend_requested": BACKEND,
        "backend": resolve_backend(BACKEND).name,
        # prefilter/brute-force rows are pure matmul scans with no engine
        # backend; the backend fields describe every compass/navix/postfilter
        # row in the file.
        "backend_applies_to": ["compass*", "navix", "postfilter"],
        "jax_version": jax.__version__,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "n": N,
        "d": D,
        # full-precision per-row footprint; the quantized tier's figures
        # (codes + amortized codebooks) are in bench_quant's rows — tracked
        # here so the memory trajectory across PRs has a fixed anchor
        "bytes_per_vector_full": 4 * D,
        "n_attrs": N_ATTRS,
        "n_queries": N_QUERIES,
        "k": K,
    }

# paper-aligned defaults
EF_SWEEP = (16, 32, 64, 128, 256, 512)
DATASETS = {
    # name -> (n_modes, mode_scale): SYN-EASY has crisp modes (CRAWL/GIST
    # regime), SYN-HARD has overlapping flat structure (VIDEO/GLOVE regime)
    "SYN-EASY": dict(n_modes=64, mode_scale=3.0),
    "SYN-HARD": dict(n_modes=512, mode_scale=1.0),
}


def get_dataset(name: str):
    kw = DATASETS[name]
    x, attrs, queries = make_vector_corpus(N, D, N_ATTRS, seed=7, **kw)
    return x, attrs, queries[:N_QUERIES]


@functools.cache
def get_index(name: str, nlist: int = 128, m: int = 16):
    """The dataset's index, built from its seed once per process (never
    loaded from disk: a stored build would not follow code changes)."""
    x, attrs, _ = get_dataset(name)
    t0 = time.time()
    idx = build_index(x, attrs, BuildConfig(m=m, nlist=nlist))
    return idx, time.time() - t0


def make_workload(rng, n_queries: int, passrate: float, n_terms: int, disj: bool):
    """Range predicates with per-attribute passrate (attrs are U[0,1])."""
    preds = []
    for _ in range(n_queries):
        terms = []
        for a in range(n_terms):
            lo = rng.uniform(0, 1 - passrate)
            terms.append(P.Pred.range(a, lo, lo + passrate))
        tree = P.Pred.or_(*terms) if disj else P.Pred.and_(*terms)
        preds.append(tree.tensor(N_ATTRS, n_terms=N_ATTRS))  # pad T for shape reuse
    return P.stack_predicates(preds)


@dataclasses.dataclass
class RunResult:
    method: str
    ef: int
    recall: float
    n_dist: float
    wall_s: float
    qps: float

    def row(self):
        return (
            f"{self.method},{self.ef},{self.recall:.4f},{self.n_dist:.0f},"
            f"{self.wall_s*1e6/max(N_QUERIES,1):.0f},{self.qps:.1f}"
        )


def _finish(method, ef, res, truth, n, wall):
    r = recall(np.asarray(res.ids), np.asarray(truth.ids), np.asarray(truth.dists), n)
    nd = float(np.asarray(res.stats.n_dist).mean())
    return RunResult(method, ef, r, nd, wall, N_QUERIES / wall if wall else 0.0)


def run_method(method: str, idx, x, attrs, queries, pred, ef: int, truth) -> RunResult:
    qj = jnp.asarray(queries)
    n = x.shape[0]
    t0 = time.time()
    if method == "compass":
        res = compass_search(idx, qj, pred, CompassParams(k=K, ef=ef, backend=BACKEND))
        res.ids.block_until_ready()
    elif method == "compass_graph":  # ablation handled by caller's index
        res = compass_search(idx, qj, pred, CompassParams(k=K, ef=ef, backend=BACKEND))
        res.ids.block_until_ready()
    elif method == "compass_relational":
        res = compass_search(
            idx, qj, pred, CompassParams(k=K, ef=ef, use_graph=False, backend=BACKEND)
        )
        res.ids.block_until_ready()
    elif method == "navix":
        res = navix_search(idx, qj, pred, CompassParams(k=K, ef=ef, backend=BACKEND))
        res.ids.block_until_ready()
    elif method == "postfilter":
        res = postfilter_search(idx, qj, pred, K, ef0=ef, backend=BACKEND)
        res.ids.block_until_ready()
    elif method == "prefilter":
        bf = prefilter_search(idx, qj, pred, K)
        bf.ids.block_until_ready()
        wall = time.time() - t0
        r = recall(np.asarray(bf.ids), np.asarray(truth.ids), np.asarray(truth.dists), n)
        return RunResult(method, ef, r, float(n), wall, N_QUERIES / wall)
    else:
        raise ValueError(method)
    wall = time.time() - t0
    return _finish(method, ef, res, truth, n, wall)


def ground_truth(x, attrs, queries, pred):
    return brute_force(jnp.asarray(x), jnp.asarray(attrs), jnp.asarray(queries), pred, K)


def find_ef_for_recall(method, idx, x, attrs, queries, pred, target, truth):
    """Smallest swept ef reaching the recall target (paper's protocol:
    report QPS at fixed recall).  Returns (RunResult, reached)."""
    best = None
    for ef in EF_SWEEP:
        rr = run_method(method, idx, x, attrs, queries, pred, ef, truth)
        best = rr
        if rr.recall >= target:
            return rr, True
    return best, False
