"""Paper Fig. 6/7 + Table V (bottom): disjunctive range filtering, 1-4
attributes at 30% per-attribute passrate (overall 30% -> ~76%)."""
from __future__ import annotations

import numpy as np

from . import common as C


def run(target_recall: float = 0.9, dataset: str = "SYN-EASY", out=print):
    idx_host, _ = C.get_index(dataset)
    idx = idx_host
    x, attrs, queries = C.get_dataset(dataset)
    rng = np.random.default_rng(1)
    out(f"# disjunctions dataset={dataset} target_recall={target_recall}")
    out("method,n_attrs,ef,recall,ndist,us_per_query,qps")
    rows = []
    for n_terms in (1, 2, 3, 4):
        pred = C.make_workload(rng, C.N_QUERIES, 0.3, n_terms, disj=True)
        truth = C.ground_truth(x, attrs, queries, pred)
        for method in ("compass", "navix", "postfilter"):
            rr, reached = C.find_ef_for_recall(
                method, idx, x, attrs, queries, pred, target_recall, truth
            )
            flag = "" if reached and rr.recall >= target_recall else "*"
            out(
                f"{method}{flag},{n_terms},{rr.ef},{rr.recall:.4f},{rr.n_dist:.0f},"
                f"{rr.wall_s*1e6/C.N_QUERIES:.0f},{rr.qps:.1f}"
            )
            rows.append((method, n_terms, rr, reached))
    return rows


def main():
    run()


if __name__ == "__main__":
    main()
