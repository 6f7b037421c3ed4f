"""Paper Figs. 8-10: recall vs #Comp/QPS curves by sweeping ef, at three
single-attribute selectivities: 80% (not selective), 30% (default), 1%
(selective)."""
from __future__ import annotations

import numpy as np

from . import common as C


def run(dataset: str = "SYN-EASY", out=print):
    idx_host, _ = C.get_index(dataset)
    idx = idx_host
    x, attrs, queries = C.get_dataset(dataset)
    rng = np.random.default_rng(2)
    out(f"# qps_recall dataset={dataset}")
    out("selectivity,method,ef,recall,ndist,us_per_query,qps")
    rows = []
    for passrate in (0.8, 0.3, 0.01):
        pred = C.make_workload(rng, C.N_QUERIES, passrate, 1, disj=False)
        truth = C.ground_truth(x, attrs, queries, pred)
        for method in ("compass", "navix", "prefilter"):
            efs = C.EF_SWEEP if method != "prefilter" else (0,)
            for ef in efs:
                rr = C.run_method(method, idx, x, attrs, queries, pred, ef, truth)
                out(
                    f"{passrate},{method},{ef},{rr.recall:.4f},{rr.n_dist:.0f},"
                    f"{rr.wall_s*1e6/C.N_QUERIES:.0f},{rr.qps:.1f}"
                )
                rows.append((passrate, method, rr))
                if rr.recall >= 0.999:
                    break
    return rows


def main():
    run()


if __name__ == "__main__":
    main()
