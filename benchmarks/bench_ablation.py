"""Paper Fig. 11 (ablation): CompassRelational (no graph) and CompassGraph
(nlist=1) against full Compass, at the default 30% passrate, on an easy and
a hard dataset."""
from __future__ import annotations

import numpy as np

from . import common as C


def run(out=print):
    rng = np.random.default_rng(3)
    rows = []
    out("# ablation passrate=0.3")
    out("dataset,method,ef,recall,ndist,us_per_query,qps")
    for dataset in ("SYN-EASY", "SYN-HARD"):
        x, attrs, queries = C.get_dataset(dataset)
        idx_full = C.get_index(dataset)[0]
        idx_g1 = C.get_index(dataset, nlist=1)[0]
        pred = C.make_workload(rng, C.N_QUERIES, 0.3, 1, disj=False)
        truth = C.ground_truth(x, attrs, queries, pred)
        for method, idx in (
            ("compass", idx_full),
            ("compass_relational", idx_full),
            ("compass_graph", idx_g1),
        ):
            for ef in C.EF_SWEEP:
                rr = C.run_method(method, idx, x, attrs, queries, pred, ef, truth)
                out(
                    f"{dataset},{method},{ef},{rr.recall:.4f},{rr.n_dist:.0f},"
                    f"{rr.wall_s*1e6/C.N_QUERIES:.0f},{rr.qps:.1f}"
                )
                rows.append((dataset, method, rr))
                if rr.recall >= 0.999:
                    break
    return rows


def main():
    run()


if __name__ == "__main__":
    main()
