"""Quantized-tier sweep: recall@k / QPS / bytes-per-vector over the PQ
configuration space, against the exact baseline (DESIGN.md §Quantization).

Two experiments per dataset:

  * **search sweep** — ``m ∈ {4, 8, 16}`` subspaces × ``refine_factor ∈
    {1, 2, 4}``, on a conjunction, a disjunction, and a ≤1%-selectivity
    workload.  Each point runs the identical query batch through the
    two-stage quantized search (ADC candidate generation + exact rerank)
    and the exact engine; ``recall_vs_exact`` is the quantized run scored
    against the exact run's results (the rerank contract: → 1.0 as
    ``refine_factor`` grows), ``recall`` against brute-force ground truth.
  * **scan microbench** — the raw hot-path comparison behind the cost
    model's ``COST_ADC_ROW``: one full-corpus predicate-filtered scan per
    query through ``scan_scores_quantized`` (the pq_score (B, N) grid /
    its jnp twin) vs ``scan_scores`` (``filter_distance``).  ADC moves
    ``m`` bytes per row instead of ``4·d``, which is the whole pitch.

Timed runs are preceded by an untimed warmup so QPS measures steady-state
execution, not XLA compilation (both arms equally).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine.backend import resolve_backend
from repro.core.quant import (
    QuantConfig,
    QuantParams,
    build_luts,
    quantize_index,
)
from repro.compass import CompassParams, compass_search

from . import common as C

M_SWEEP = (4, 8, 16)
REFINE_SWEEP = (1, 2, 4)
EF = 64
KMEANS_ITERS = 8


def _workloads(rng):
    """(name, (B, T, A) predicate batch) for the three required shapes."""
    conj = C.make_workload(rng, C.N_QUERIES, passrate=0.45, n_terms=2, disj=False)
    disj = C.make_workload(rng, C.N_QUERIES, passrate=0.10, n_terms=4, disj=True)
    # ≤1% overall selectivity: two-term conjunction at 10% per attribute
    narrow = C.make_workload(rng, C.N_QUERIES, passrate=0.10, n_terms=2, disj=False)
    return (("conj", conj), ("disj", disj), ("narrow", narrow))


def _timed(idx, qj, pred, pm):
    res = compass_search(idx, qj, pred, pm)  # warmup: compile + cache
    res.ids.block_until_ready()
    t0 = time.time()
    res = compass_search(idx, qj, pred, pm)
    res.ids.block_until_ready()
    return res, time.time() - t0


def _scan_microbench(qidx, queries, pred, backend, metric="l2", reps: int = 5):
    """Full-corpus filtered scan QPS: ADC codes vs float32 rows.

    Both arms run as one jitted program (how the engine consumes them —
    eager per-op dispatch would swamp the row-scoring cost being compared);
    the ADC arm includes its per-query LUT construction, which is part of
    every real ADC scan.
    """
    n = qidx.n_records
    b = queries.shape[0]
    ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    mask = jnp.ones((b, n), bool)

    @jax.jit
    def adc(qs):
        luts = build_luts(qidx.qvecs, qs, metric)
        d, p = backend.scan_scores_quantized(qidx, luts, pred, ids, mask, metric)
        return d, p

    @jax.jit
    def exact(qs):
        return backend.scan_scores(qidx, qs, pred, ids, mask, metric)

    out = {}
    for name, fn in (("adc_scan", adc), ("exact_scan", exact)):
        fn(queries)[0].block_until_ready()  # warmup: compile
        t0 = time.time()
        for _ in range(reps):
            fn(queries)[0].block_until_ready()
        wall = (time.time() - t0) / reps
        out[name] = {"method": name, "qps": b / wall if wall else 0.0, "wall_s": wall}
    return out


def run(dataset: str = "SYN-EASY", out=print):
    idx_host, _ = C.get_index(dataset)
    x, attrs, queries = C.get_dataset(dataset)
    qj = jnp.asarray(queries)
    rng = np.random.default_rng(5)
    backend = resolve_backend(C.BACKEND)
    workloads = _workloads(rng)
    out(f"# quant sweep dataset={dataset} ef={EF} n={C.N} d={C.D}")
    out("workload,m,refine,bytes/vec,quant_qps,exact_qps,recall_vs_exact,recall")
    rows = []
    pm_exact = CompassParams(k=C.K, ef=EF, backend=C.BACKEND)
    exact_runs = {}
    truths = {}
    for name, pred in workloads:
        truths[name] = C.ground_truth(x, attrs, queries, pred)
        res, wall = _timed(idx_host, qj, pred, pm_exact)
        exact_runs[name] = (res, C._finish("exact", EF, res, truths[name], C.N, wall))
    for m in M_SWEEP:
        qidx = quantize_index(
            idx_host, QuantConfig(m=m, iters=KMEANS_ITERS)
        )
        bpv = qidx.qvecs.bytes_per_vector
        for name, pred in workloads:
            exact_res, exact_rr = exact_runs[name]
            for rf in REFINE_SWEEP:
                pm_q = CompassParams(
                    k=C.K, ef=EF, backend=C.BACKEND, quant=QuantParams(refine_factor=rf)
                )
                res, wall = _timed(qidx, qj, pred, pm_q)
                rr = C._finish(f"quant_m{m}_rf{rf}", EF, res, truths[name], C.N, wall)
                r_vs_exact = C.recall(
                    np.asarray(res.ids),
                    np.asarray(exact_res.ids),
                    np.asarray(exact_res.dists),
                    C.N,
                )
                rows.append(
                    {
                        "workload": name,
                        "m": m,
                        "refine_factor": rf,
                        "bytes_per_vector": bpv,
                        "compression": 4.0 * C.D / bpv,
                        "recall_vs_exact": r_vs_exact,
                        "quant": dataclasses.asdict(rr),
                        "exact": dataclasses.asdict(exact_rr),
                    }
                )
                out(
                    f"{name},{m},{rf},{bpv:.1f},{rr.qps:.1f},{exact_rr.qps:.1f},"
                    f"{r_vs_exact:.4f},{rr.recall:.4f}"
                )
        # scan microbench once per m (refine_factor plays no role in a scan)
        scan_pred = workloads[0][1]
        scans = _scan_microbench(qidx, qj, scan_pred, backend)
        rows.append(
            {
                "workload": "scan",
                "m": m,
                "refine_factor": 0,
                "bytes_per_vector": bpv,
                "compression": 4.0 * C.D / bpv,
                "adc_scan": scans["adc_scan"],
                "exact_scan": scans["exact_scan"],
            }
        )
        out(
            f"scan,{m},-,{bpv:.1f},adc={scans['adc_scan']['qps']:.1f},"
            f"exact={scans['exact_scan']['qps']:.1f}"
        )
    return rows


def main():
    run()


if __name__ == "__main__":
    main()
