"""Kernel microbenchmarks: the per-visit-step hot path in isolation.

Three experiments, all timed as steady-state jitted programs (untimed
warmup compiles both arms equally):

  * **visit_step** — the fused gather + distance + DNF predicate +
    tombstone + admission kernel (``kernels/visit_step.py``) against the
    unfused composition it replaced (``filter_distance`` kernel + jnp
    live gather + admission select), over a (d, V) sweep for both "l2"
    and "ip".  This is the engine's per-step hot spot: the fused kernel
    saves one full gather of the visit rows plus two intermediate
    materializations per step.
  * **pq_score** — the ADC kernel over an m sweep (subspace count is the
    bytes-moved knob), pallas vs the jnp ref path.  The adc/exact row
    cost ratio behind the planner's ``COST_ADC_ROW`` constant.
  * **ivf_score** — the blocked centroid-ranking matmul at two nlist
    shapes, pallas vs ref.

On CPU the pallas arms execute in interpret mode, so absolute QPS and
even fused-vs-unfused ordering are *advisory* there (the interpreter
pays per-ref-access Python overhead the Mosaic lowering doesn't); the
compiled-TPU path is where the fused kernel must win at every (d, V).
The committed baseline records the CPU-interpret numbers to keep the
trajectory attributable; ``meta.backend``/``platform`` say which regime
a given artifact measured.

The final row records the autotuner's decisions
(``kernels/autotune.decisions``: each shape's block config and whether it
was pinned, measured or the default) so an artifact records *which* block
configs produced its numbers.

``python -m benchmarks.bench_kernels --selfcheck`` runs the fallback
tripwire only: it fails (SystemExit) if the engine's pallas backend
stops routing VISIT through the fused kernel — the regression CI must
catch loudly, because the ref fallback is silent by design.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import autotune, ops, ref

D_SWEEP = (16, 48)
V_SWEEP = (64, 256)
M_SWEEP = (4, 8, 16)
NLIST_SWEEP = (64, 256)
METRICS = ("l2", "ip")
N_ROWS = 4096
B = 16
N_ATTRS = 4
N_TERMS = 2
REPS = 3


def _mk_problem(rng, d: int, v: int):
    """Corpus rows + a per-query visit batch shaped like the engine's."""
    n = N_ROWS
    vecs = np.concatenate(
        [rng.normal(size=(n, d)).astype(np.float32), np.zeros((1, d), np.float32)]
    )
    attrs = np.concatenate(
        [
            rng.uniform(size=(n, N_ATTRS)).astype(np.float32),
            np.full((1, N_ATTRS), np.inf, np.float32),
        ]
    )
    live = np.ones(n + 1, bool)
    live[rng.integers(0, n, size=n // 10)] = False
    idx = rng.integers(0, n, size=(B, v)).astype(np.int32)
    mask = np.ones((B, v), bool)
    q = rng.normal(size=(B, d)).astype(np.float32)
    lo = np.full((N_TERMS, N_ATTRS), -np.inf, np.float32)
    hi = np.full((N_TERMS, N_ATTRS), np.inf, np.float32)
    lo[0, 0], hi[0, 0] = 0.2, 0.8
    return tuple(jnp.asarray(a) for a in (vecs, attrs, live, idx, mask, q, lo, hi))


def _time_fn(fn, *args, reps: int = REPS) -> float:
    """Steady-state seconds per call (min over reps after a warmup)."""
    jax.block_until_ready(fn(*args))
    best = np.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _arm(method: str, wall: float) -> dict:
    return {"method": method, "qps": B / wall if wall else 0.0, "wall_s": wall}


def _visit_bench(rng, d: int, v: int, metric: str) -> dict:
    vecs, attrs, live, idx, mask, q, lo, hi = _mk_problem(rng, d, v)

    @jax.jit
    def fused(qs, ids):
        return jax.vmap(
            lambda q1, i1, m1: ops.visit_step(
                vecs, attrs, live, i1, m1, q1, lo, hi, metric=metric
            )
        )(qs, ids, mask)

    @jax.jit
    def unfused(qs, ids):
        # the pre-fusion engine sequence: filter_distance kernel, then the
        # jnp tombstone gather, then the admission select
        def one(q1, i1, m1):
            dist, passing = ops.filter_distance(
                vecs, attrs, i1, m1, q1, lo, hi, metric=metric
            )
            passing = passing & m1 & live[i1]
            return dist, jnp.where(passing, dist, jnp.inf)

        return jax.vmap(one)(qs, ids, mask)

    row = {
        "kernel": "visit_step",
        "metric": metric,
        "d": d,
        "v": v,
        "fused": _arm("fused_visit", _time_fn(fused, q, idx)),
        "unfused": _arm("unfused_visit", _time_fn(unfused, q, idx)),
    }
    row["fused_speedup"] = row["fused"]["qps"] / max(row["unfused"]["qps"], 1e-9)
    return row


def _pq_bench(rng, m: int, metric: str, v: int = 256, ks: int = 16) -> dict:
    d = m * 4  # dsub = 4
    vecs, attrs, live, idx, mask, q, lo, hi = _mk_problem(rng, d, v)
    codes = jnp.asarray(
        np.concatenate(
            [
                rng.integers(0, ks, size=(N_ROWS, m)).astype(np.uint8),
                np.zeros((1, m), np.uint8),
            ]
        )
    )
    codebooks = jnp.asarray(rng.normal(size=(m, ks, 4)).astype(np.float32))

    def make(use_pallas):
        @jax.jit
        def f(qs, ids):
            luts = jax.vmap(lambda q1: ref.adc_lut(codebooks, q1, metric))(qs)
            return jax.vmap(
                lambda t1, i1, m1: ops.pq_score(
                    codes, attrs, i1, m1, t1, lo, hi, use_pallas=use_pallas
                )
            )(luts, ids, mask)

        return f

    return {
        "kernel": "pq_score",
        "metric": metric,
        "d": d,
        "v": v,
        "m": m,
        "pallas": _arm("pq_pallas", _time_fn(make(True), q, idx)),
        "ref": _arm("pq_ref", _time_fn(make(False), q, idx)),
    }


def _ivf_bench(rng, nlist: int, metric: str, d: int = 48) -> dict:
    qs = jnp.asarray(rng.normal(size=(B, d)).astype(np.float32))
    cents = jnp.asarray(rng.normal(size=(nlist, d)).astype(np.float32))
    pal = jax.jit(lambda a, b: ops.ivf_score(a, b, metric=metric))
    ref = jax.jit(lambda a, b: ops.ivf_score(a, b, metric=metric, use_pallas=False))
    return {
        "kernel": "ivf_score",
        "metric": metric,
        "d": d,
        "v": nlist,
        "pallas": _arm("ivf_pallas", _time_fn(pal, qs, cents)),
        "ref": _arm("ivf_ref", _time_fn(ref, qs, cents)),
    }


def selfcheck() -> None:
    """Tripwire: the engine's pallas backend must reach the fused kernel.

    ``visit_step.TRACE_COUNT`` advances every time the kernel *wrapper* is
    traced; a refactor that reroutes PallasBackend.visit_step to the ref
    composition (or a guard that starts rejecting "l2") would leave it
    flat — silently, because the fallback is behavioral parity by design.
    Exercised at both the ops layer and through a full compass_search.
    """
    from repro.core import predicate as P
    from repro.core.engine.backend import PallasBackend
    from repro.core.index import BuildConfig, build_index
    from repro.compass import CompassParams, compass_search
    import repro.kernels.visit_step as vs

    rng = np.random.default_rng(0)
    vecs, attrs, live, idx, mask, q, lo, hi = _mk_problem(rng, 16, 32)

    before = vs.TRACE_COUNT
    jax.block_until_ready(
        jax.jit(
            lambda: ops.visit_step(
                vecs, attrs, live, idx[0], mask[0], q[0], lo, hi, metric="l2"
            )
        )()
    )
    if vs.TRACE_COUNT <= before:
        raise SystemExit("selfcheck FAIL: ops.visit_step did not trace the fused kernel")

    n, d, a = 500, 8, 2
    x = rng.normal(size=(n, d)).astype(np.float32)
    at = rng.uniform(size=(n, a)).astype(np.float32)
    index = build_index(x, at, BuildConfig(m=8, nlist=8))
    plo = np.full((2, 1, a), -np.inf, np.float32)
    phi = np.full((2, 1, a), np.inf, np.float32)
    plo[:, 0, 0] = 0.2
    pred = P.Predicate(jnp.asarray(plo), jnp.asarray(phi))
    queries = jnp.asarray(rng.normal(size=(2, d)).astype(np.float32))

    before = vs.TRACE_COUNT
    res = compass_search(index, queries, pred, CompassParams(backend="pallas"))
    jax.block_until_ready(res.ids)
    if vs.TRACE_COUNT <= before:
        raise SystemExit(
            "selfcheck FAIL: compass_search(backend='pallas') never traced the "
            "fused visit_step kernel — VISIT is silently on the ref/unfused path"
        )
    assert isinstance(PallasBackend().visit_step, object)  # surface still exists
    print(f"selfcheck ok: fused visit_step traced (TRACE_COUNT={vs.TRACE_COUNT})")


def run(out=print):
    rng = np.random.default_rng(13)
    out(f"# kernel microbench n={N_ROWS} b={B} reps={REPS}")
    rows = []
    out("kernel,metric,d,v,extra,arm_a_qps,arm_b_qps")
    for metric in METRICS:
        for d in D_SWEEP:
            for v in V_SWEEP:
                row = _visit_bench(rng, d, v, metric)
                rows.append(row)
                out(
                    f"visit_step,{metric},{d},{v},speedup={row['fused_speedup']:.2f},"
                    f"{row['fused']['qps']:.1f},{row['unfused']['qps']:.1f}"
                )
    for metric in METRICS:
        for m in M_SWEEP:
            row = _pq_bench(rng, m, metric)
            rows.append(row)
            out(
                f"pq_score,{metric},{row['d']},{row['v']},m={m},"
                f"{row['pallas']['qps']:.1f},{row['ref']['qps']:.1f}"
            )
    for metric in METRICS:
        for nlist in NLIST_SWEEP:
            row = _ivf_bench(rng, nlist, metric)
            rows.append(row)
            out(
                f"ivf_score,{metric},{row['d']},{nlist},-,"
                f"{row['pallas']['qps']:.1f},{row['ref']['qps']:.1f}"
            )
    # provenance: which block configs produced the numbers above, and
    # where each came from (pin / table / measured / default)
    rows.append(
        {"kernel": "autotune_table", "metric": "-", "d": 0, "v": 0,
         "table": autotune.decisions()}
    )
    return rows


def main():
    if "--selfcheck" in sys.argv[1:]:
        selfcheck()
        return
    run()


if __name__ == "__main__":
    main()
