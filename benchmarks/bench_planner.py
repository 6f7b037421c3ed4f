"""Planner selectivity sweep: chosen execution mode, QPS, and recall as the
predicate pass rate walks from 1.0 down to 1e-3, single- and
multi-attribute (the crossover experiment behind DESIGN.md §Planner).

Each point runs the same workload twice — planner-enabled vs
forced-COOPERATIVE (``planner=False``, i.e. the pre-planner engine) — so a
row directly exhibits the mode the cost model picked and what it bought.
Timed runs are preceded by an untimed warmup call so QPS measures
steady-state execution, not XLA compilation (both arms equally).
"""
from __future__ import annotations

import dataclasses
import time

import jax.numpy as jnp
import numpy as np

from repro.core.planner.plan import MODE_NAMES
from repro.compass import CompassParams, compass_search

from . import common as C

# overall target pass rates for the sweep (paper regime: robust from
# vacuous filters down to needle-in-haystack)
PASSRATES = (1.0, 0.5, 0.2, 0.1, 0.03, 0.01, 0.003, 0.001)
EF = 64


def _timed(idx, qj, pred, pm):
    res = compass_search(idx, qj, pred, pm)  # warmup: compile + cache
    res.ids.block_until_ready()
    t0 = time.time()
    res = compass_search(idx, qj, pred, pm)
    res.ids.block_until_ready()
    wall = time.time() - t0
    return res, wall


def _mode_counts(res) -> dict:
    modes = np.asarray(res.stats.mode)
    return {name: int(np.sum(modes == m)) for m, name in enumerate(MODE_NAMES)}


def run(dataset: str = "SYN-EASY", out=print):
    idx_host, _ = C.get_index(dataset)
    idx = idx_host
    x, attrs, queries = C.get_dataset(dataset)
    qj = jnp.asarray(queries)
    rng = np.random.default_rng(0)
    out(f"# planner sweep dataset={dataset} ef={EF} n={C.N}")
    out("workload,passrate,modes,planner_qps,cooperative_qps,planner_recall,cooperative_recall")
    rows = []
    for workload, n_terms in (("single", 1), ("multi", 2)):
        for target in PASSRATES:
            per_attr = target ** (1.0 / n_terms)  # conjunction of U[0,1] ranges
            pred = C.make_workload(rng, C.N_QUERIES, per_attr, n_terms, disj=False)
            truth = C.ground_truth(x, attrs, queries, pred)
            pm_on = CompassParams(k=C.K, ef=EF, planner=True, backend=C.BACKEND)
            pm_off = CompassParams(k=C.K, ef=EF, planner=False, backend=C.BACKEND)
            res_on, wall_on = _timed(idx, qj, pred, pm_on)
            res_off, wall_off = _timed(idx, qj, pred, pm_off)
            rr_on = C._finish("planner", EF, res_on, truth, C.N, wall_on)
            rr_off = C._finish("cooperative", EF, res_off, truth, C.N, wall_off)
            modes = _mode_counts(res_on)
            row = {
                "workload": workload,
                "n_terms": n_terms,
                "passrate": target,
                "mode_counts": modes,
                "planner": dataclasses.asdict(rr_on),
                "cooperative": dataclasses.asdict(rr_off),
            }
            rows.append(row)
            mode_str = "/".join(f"{k}:{v}" for k, v in modes.items() if v)
            out(
                f"{workload},{target},{mode_str},{rr_on.qps:.1f},{rr_off.qps:.1f},"
                f"{rr_on.recall:.4f},{rr_off.recall:.4f}"
            )
    return rows


def main():
    run()


if __name__ == "__main__":
    main()
