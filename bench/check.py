"""The comparison that decides ``correct``.

Every answer delivered in the window (or a sample of at most
``MAX_COMPARED`` drawn from the seed) is compared with the plain reference
(:mod:`bench.reference`), once the window has closed:

* ``recall``   -- mean recall@k against the exact filtered top-k, as in the
  paper's Eq. (1); a returned row whose float64 distance ties the k-th
  true distance counts as found.  Held to the configuration's stated floor.
* ``dist_err`` -- the largest gap between a returned distance and the same
  row's distance in float64, over ``|q| |x|`` (the scale of a float32
  distance's rounding).  The configurations state float32 distances for
  every row returned; the limit sits between sound runs and the control.
* ``bad_rows`` -- returned rows that say something wrong: an id outside the
  corpus with a finite distance, a valid id with no finite distance, an id
  twice in one answer, or a row that fails the query's predicate.  Exact.
* ``lost``     -- requests dispatched in the window whose answer never came.
  Exact.
"""
from __future__ import annotations

import numpy as np

from . import reference

#: answers compared at most per run, drawn from the seed when there are more
MAX_COMPARED = 1024
#: candidates per query that the device scan keeps for the float64 re-score
DEPTH = 64
#: limits of the exact comparisons
EXACT_LIMITS = {"bad_rows": 0, "lost": 0}


def _passes_host(attrs_rows, lo, hi):
    a = attrs_rows[:, None, :]
    return np.any(np.all((a >= lo) & (a <= hi), axis=-1), axis=-1)


def compare(answers, lost: int, x, attrs, queries, preds, k: int, metric: str,
            limits: dict, rng: np.random.Generator, batch: int = 32):
    """Compare ``answers`` (``(pool index, ids, dists)`` each) with the
    reference.  Returns ``(correct, numbers)``; ``numbers`` maps each name to
    ``{"value", "limit", "holds"}`` where ``holds`` is ``">="`` or ``"<="``."""
    import jax.numpy as jnp

    if len(answers) > MAX_COMPARED:
        keep = np.sort(rng.choice(len(answers), MAX_COMPARED, replace=False))
        answers = [answers[i] for i in keep]
    n = x.shape[0]
    xd, ad = jnp.asarray(x), jnp.asarray(attrs)
    hits = total = bad = 0
    err = 0.0
    # group by predicate shape so each scan call has one (B, T, A)
    by_t: dict[int, list] = {}
    for a in answers:
        by_t.setdefault(preds[a[0]][0].shape[0], []).append(a)
    for group in by_t.values():
        for s in range(0, len(group), batch):
            part = group[s : s + batch]
            idx = [a[0] for a in part]
            pad = batch - len(part)
            qs = np.concatenate([queries[idx], np.zeros((pad, x.shape[1]), np.float32)])
            lo = np.stack([preds[i][0] for i in idx] + [preds[idx[0]][0]] * pad)
            hi = np.stack([preds[i][1] for i in idx] + [preds[idx[0]][1]] * pad)
            cand, _ = reference.scan(xd, ad, jnp.asarray(qs), jnp.asarray(lo),
                                     jnp.asarray(hi), depth=DEPTH, metric=metric,
                                     precision="highest")
            cand = np.asarray(cand)
            for j, (qi, ids, dists) in enumerate(part):
                q = queries[qi]
                t_ids, t_d = reference.truth(x, cand[j], q, k, metric)
                ids = np.asarray(ids, np.int64)[:k]
                dists = np.asarray(dists, np.float64)[:k]
                valid = (ids >= 0) & (ids < n)
                bad += int(np.sum(~valid & np.isfinite(dists)))
                bad += int(np.sum(valid & ~np.isfinite(dists)))
                rows = ids[valid & np.isfinite(dists)]
                rd = dists[valid & np.isfinite(dists)]
                bad += len(rows) - len(np.unique(rows))
                lo_q, hi_q = preds[qi]
                ok = _passes_host(attrs[rows], lo_q, hi_q)
                bad += int(np.sum(~ok))
                d64 = reference.distances64(x[rows], q, metric)
                if len(rows):
                    scale = np.linalg.norm(q.astype(np.float64)) * np.linalg.norm(
                        x[rows].astype(np.float64), axis=1)
                    err = max(err, float(np.max(np.abs(rd - d64) / np.maximum(scale, 1e-30))))
                if len(t_ids):
                    kth = t_d[-1]
                    found = np.unique(rows[ok & (d64 <= kth + 1e-9 * abs(kth))])
                    hits += min(len(found), len(t_ids))
                    total += len(t_ids)
    numbers = {
        "recall": {"value": hits / max(total, 1), "limit": limits["recall"], "holds": ">="},
        "dist_err": {"value": err, "limit": limits["dist_err"], "holds": "<="},
        "bad_rows": {"value": bad, "limit": EXACT_LIMITS["bad_rows"], "holds": "<="},
        "lost": {"value": lost, "limit": EXACT_LIMITS["lost"], "holds": "<="},
    }
    correct = bool(answers) and all(
        (v["value"] >= v["limit"]) if v["holds"] == ">=" else (v["value"] <= v["limit"])
        for v in numbers.values()
    )
    return correct, numbers
