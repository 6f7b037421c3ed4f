"""Serving-layer benchmark: QPS / latency / compile counts for the
continuous-batching SearchService under a mixed predicate-shape workload.

Not a paper figure — the serving subsystem is our production extension —
but directly motivated by Compass §VI: throughput under mixed hybrid
workloads is decided by batching and routing, not just per-query latency.

Three interleaved shape classes:
  * ``conj2``  — 2-attribute conjunction, 30% per-attr passrate (T=1)
  * ``disj4``  — 4-way single-attribute disjunction (T=4)
  * ``hisel3`` — high-selectivity 3-attribute conjunction, 10% passrate (T=1)

The stream occupies two (B, T) buckets; the measured invariants are (a)
total XLA compiles == occupied buckets, steady state included, and (b)
every service response is bitwise-identical to the corresponding direct
``compass_search`` call (checked on a subsample, recorded as
``bitwise_ok``).
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core import predicate as P
from repro.compass import CompassParams, compass_search
from repro.serving.search_service import SearchService

from . import common as C

EF = 64
BATCH = 8
MAX_WAIT_S = 0.005

SHAPE_CLASSES = ("conj2", "disj4", "hisel3")


def _make_pred(rng, cls: str) -> P.Pred:
    if cls == "conj2":
        return P.Pred.and_(*[_rng_range(rng, a, 0.3) for a in range(2)])
    if cls == "disj4":
        return P.Pred.or_(*[_rng_range(rng, a, 0.3) for a in range(4)])
    if cls == "hisel3":
        return P.Pred.and_(*[_rng_range(rng, a, 0.1) for a in range(3)])
    raise ValueError(cls)


def _rng_range(rng, attr: int, passrate: float) -> P.Pred:
    lo = rng.uniform(0, 1 - passrate)
    return P.Pred.range(attr, lo, lo + passrate)


def _percentile(xs, p):
    return float(np.percentile(np.asarray(xs), p)) if len(xs) else 0.0


def run(dataset: str = "SYN-EASY", out=print):
    idx_host, _ = C.get_index(dataset)
    idx = idx_host
    _, _, queries = C.get_dataset(dataset)
    rng = np.random.default_rng(11)
    pm = CompassParams(k=C.K, ef=EF, backend=C.BACKEND)
    service = SearchService(idx, pm, batch_size=BATCH, max_wait_s=MAX_WAIT_S)

    n_requests = 3 * C.N_QUERIES
    workload = [
        (SHAPE_CLASSES[i % 3], queries[i % len(queries)], _make_pred(rng, SHAPE_CLASSES[i % 3]))
        for i in range(n_requests)
    ]

    def drive():
        t0 = time.time()
        rid_job = {}  # rid -> (class, query, pred tree), in submission order
        for cls, q, tree in workload:
            rid_job[service.submit(q, tree, k=C.K)] = (cls, q, tree)
            service.step()
        results = {r.rid: r for r in service.flush()}
        for rid in rid_job:
            results.setdefault(rid, service.poll(rid))
        wall = time.time() - t0
        lat = {c: [] for c in SHAPE_CLASSES}
        for rid, (cls, _, _) in rid_job.items():
            r = results[rid]
            lat[cls].append(r.queue_wait_s + r.batch_exec_s)
        return wall, lat, rid_job, results

    # pass 1 pays the per-bucket compiles; pass 2 is steady state
    warm_wall, _, _, _ = drive()
    compiles_after_warmup = service.compile_count
    steady_wall, lat, rid_job, results = drive()
    stats = service.stats()

    assert service.compile_count == compiles_after_warmup, "steady state recompiled"
    assert stats["compiles"] == stats["occupied_buckets"], stats

    # bitwise parity vs direct compass_search on a subsample
    sample = list(rid_job.items())[:: max(1, n_requests // 24)]
    bitwise_ok = True
    for rid, (_cls, q, tree) in sample:
        direct = compass_search(
            idx, jnp.asarray(q[None]),
            P.stack_predicates([tree.tensor(C.N_ATTRS)]), pm,
        )
        r = results[rid]
        bitwise_ok &= np.array_equal(r.ids, np.asarray(direct.ids)[0, : C.K])
        bitwise_ok &= np.array_equal(
            r.dists.view(np.uint32), np.asarray(direct.dists)[0, : C.K].view(np.uint32)
        )
    assert bitwise_ok, "service response != direct compass_search"

    out(f"# serving dataset={dataset} B={BATCH} max_wait={MAX_WAIT_S*1e3:.1f}ms")
    out("class,n,lat_p50_ms,lat_p99_ms")
    per_class = {}
    for cls in SHAPE_CLASSES:
        p50, p99 = _percentile(lat[cls], 50) * 1e3, _percentile(lat[cls], 99) * 1e3
        out(f"{cls},{len(lat[cls])},{p50:.2f},{p99:.2f}")
        per_class[cls] = {"n": len(lat[cls]), "lat_p50_ms": p50, "lat_p99_ms": p99}
    qps = n_requests / steady_wall if steady_wall else 0.0
    out(
        f"steady_qps={qps:.1f} compiles={stats['compiles']} "
        f"occupied_buckets={stats['occupied_buckets']} bitwise_ok={bitwise_ok}"
    )
    return {
        "n_requests_per_pass": n_requests,
        "warmup_wall_s": warm_wall,
        "steady_wall_s": steady_wall,
        "steady_qps": qps,
        "per_class": per_class,
        "bitwise_ok": bool(bitwise_ok),
        "service": stats,
    }


def main():
    run()


if __name__ == "__main__":
    main()
