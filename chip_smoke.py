#!/usr/bin/env python3
"""Bring-up smoke test: the served filtered-search path on a TPU.

    python chip_smoke.py                  # one chip: the served path at 1M x 128
    python chip_smoke.py --chips 4        # four chips: only the sharded path
    python chip_smoke.py --cpu-rehearsal  # CPU, Pallas in interpret mode, tiny

One chip: generates a SIFT1M-shaped corpus from ``--seed`` (1,000,000 x
128 float32 rows with 4 uniform float attributes, paper §V.A), builds the
Compass index on the device (nlist ~ sqrt(N)), attaches a PQ tier
(m=16), and serves a few hundred filtered top-10 queries through
``CollectionService`` with the planner on: an exact collection (a
``MutableIndex``) and a PQ collection over the same corpus.  The query
mix (2-term conjunctions, 4-way disjunctions, narrow ranges) routes
through PREFILTER, COOPERATIVE and the ``pq_score`` kernel.  Results are
checked against ``core.baselines.brute_force``: recall@10 per collection
against a floor, and exact ids (up to distance ties) for PREFILTER.
Then a few upserts and a delete go through the service and are read back.

Four chips: builds 4 shards with ``build_sharded_index``, places each
shard on its own device of a 4-device ``Mesh``, runs
``make_distributed_search`` and checks recall@10 against brute force.

Every kernel must lower natively (no interpret mode), no kernel may fall
back to the jnp reference path, and the fused visit-step kernel must be
traced.  Any failed phase raises: the script then exits non-zero and does
not print its result line.  It needs a TPU unless ``--cpu-rehearsal``
is given.  The last line of stdout is the result: one JSON object.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: recall@10 floors against exact brute force, per served collection
RECALL_FLOOR = {"exact": 0.90, "pq": 0.85}
#: recall@10 floor of the four-chip sharded search
SHARDED_RECALL_FLOOR = 0.90
DIM, N_ATTRS, K = 128, 4, 10
PQ_M = 16
#: corpus rows: SIFT1M's own count
ROWS = 1_000_000
#: rows of the four-chip path, and why they are cut (printed with the cut);
#: 400,000 rows took 111 s to build on a v5e 2x2 host
FOUR_CHIP_ROWS = 400_000
FOUR_CHIP_CUT = ("the four-chip path checks shard placement and the sharded "
                 "program against brute force, not scale; its 4 shards are built "
                 "one after another on the host, about two minutes at this size")
#: queries per collection and service micro-batch
QUERIES, BATCH = 256, 32
#: CPU rehearsal sizes (Pallas in interpret mode): rows on one and on four
#: virtual devices, queries per collection, micro-batch
REHEARSAL_ROWS = {1: 4_000, 4: 8_000}
REHEARSAL_QUERIES, REHEARSAL_BATCH = 48, 8


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU with Pallas in interpret mode (no chip), tiny")
    args = ap.parse_args(argv)
    if args.cpu_rehearsal:
        args.rows = REHEARSAL_ROWS[args.chips]
        args.queries, args.batch = REHEARSAL_QUERIES, REHEARSAL_BATCH
    else:
        args.rows = FOUR_CHIP_ROWS if args.chips == 4 else ROWS
        args.queries, args.batch = QUERIES, BATCH
    return args


def log(msg: str) -> None:
    print(msg, flush=True)


class Phases:
    """Wall time of each phase, printed as it ends."""

    def __init__(self):
        self.wall: dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name):
        log(f"-- phase {name}")
        t0 = time.perf_counter()
        yield
        self.wall[name] = time.perf_counter() - t0
        log(f"-- phase {name}: {self.wall[name]:.3f} s")


class CompileLog:
    """Backend compilations seen by JAX's monitoring hook."""

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


# -- data ---------------------------------------------------------------------


def make_corpus(rng, n, n_queries):
    """SIFT1M-shaped corpus: ``n`` x 128 float32 rows near 16-dimensional
    affine patches around ~1000-row modes (real descriptor sets have a low
    intrinsic dimension), 4 uniform [0, 1) attributes, and ``n_queries``
    held-out queries from the same distribution."""
    import numpy as np

    n_modes, intrinsic = min(1024, max(16, n // 1000)), 16
    total = n + n_queries
    centers = rng.normal(size=(n_modes, DIM)).astype(np.float32) * 4.0
    basis = rng.normal(size=(n_modes, intrinsic, DIM)).astype(np.float32) * 0.5
    modes = rng.integers(0, n_modes, total)
    z = rng.normal(size=(total, intrinsic)).astype(np.float32)
    x = centers[modes] + 0.05 * rng.normal(size=(total, DIM)).astype(np.float32)
    order = np.argsort(modes, kind="stable")
    bounds = np.searchsorted(modes[order], np.arange(n_modes + 1))
    for m in range(n_modes):
        rows = order[bounds[m] : bounds[m + 1]]
        x[rows] += z[rows] @ basis[m]
    attrs = rng.uniform(size=(n, N_ATTRS)).astype(np.float32)
    return x[:n], attrs, x[n:]


def make_predicates(rng, n_queries, n_rows):
    """The traffic mix, in thirds: 2-term conjunctions (~9% pass), 4-way
    disjunctions of single-attribute ranges (~20% pass), and narrow
    single-attribute ranges of 100-200 rows (<= 0.02% at 1M rows; at least
    one row) that the planner routes to PREFILTER."""
    from repro.core import predicate as P

    kinds, preds = [], []
    for i in range(n_queries):
        kind = ("conj", "disj", "narrow")[i % 3]
        if kind == "conj":
            a0, a1 = rng.choice(N_ATTRS, 2, replace=False)
            l0, l1 = rng.uniform(0, 0.7, 2)
            tree = P.Pred.and_(P.Pred.range(a0, l0, l0 + 0.3), P.Pred.range(a1, l1, l1 + 0.3))
        elif kind == "disj":
            lows = rng.uniform(0, 0.95, N_ATTRS)
            tree = P.Pred.or_(*[P.Pred.range(a, lows[a], lows[a] + 0.05) for a in range(N_ATTRS)])
        else:
            width = max(rng.uniform(100, 200) / n_rows, 2.0 / n_rows)
            lo = rng.uniform(0, 1 - width)
            tree = P.Pred.range(int(rng.integers(N_ATTRS)), lo, lo + width)
        kinds.append(kind)
        preds.append(tree.tensor(N_ATTRS))
    return kinds, preds


# -- checks ---------------------------------------------------------------------


def assert_on_device(tree, device, what):
    """Every leaf is a jax.Array committed to ``device``: the service hands
    the index to the compiled program as an argument, so a host leaf would
    be copied to the chip on every batch."""
    import jax

    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        if not isinstance(leaf, jax.Array) or leaf.devices() != {device}:
            raise AssertionError(f"{what}{jax.tree_util.keystr(path)} is not on {device}")


def check_kernels_native(rehearsal: bool):
    from repro.kernels import interpret

    if interpret.default_interpret() != rehearsal:
        raise AssertionError(
            f"kernels resolve interpret={interpret.default_interpret()}; "
            f"expected {rehearsal}"
        )


def kernel_parity(rng, rehearsal):
    """Every kernel of the served path against its jnp oracle, on a small
    corpus at the served widths (d=128, A=4, m=16, ks=256)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    n, v, b, t = 4096, 64, 8, 4
    vectors = jnp.asarray(rng.normal(size=(n + 1, DIM)).astype(np.float32))
    attrs = rng.uniform(size=(n + 1, N_ATTRS)).astype(np.float32)
    attrs[-1] = np.inf
    attrs = jnp.asarray(attrs)
    live = jnp.asarray(rng.uniform(size=n + 1) > 0.2)
    codes = jnp.asarray(rng.integers(0, 256, (n + 1, PQ_M)).astype(np.uint8))
    luts = jnp.asarray(rng.uniform(0, 4, (b, PQ_M, 256)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, n + 1, (b, v)).astype(np.int32))
    mask = jnp.asarray(rng.uniform(size=(b, v)) > 0.2)
    qs = jnp.asarray(rng.normal(size=(b, DIM)).astype(np.float32))
    lo = jnp.asarray(rng.uniform(0, 0.5, (b, t, N_ATTRS)).astype(np.float32))
    hi = lo + 0.5
    tol = dict(rtol=1e-5, atol=1e-4)

    def same(name, got, want, exact=False):
        for g, w in zip(got, want):
            g, w = np.asarray(g), np.asarray(w)
            if g.dtype == bool or exact:
                np.testing.assert_array_equal(g, w, err_msg=name)
            else:
                np.testing.assert_array_equal(np.isfinite(g), np.isfinite(w), err_msg=name)
                fin = np.isfinite(w)
                np.testing.assert_allclose(g[fin], w[fin], err_msg=name, **tol)
        log(f"   {name}: matches its oracle")

    run = lambda f, *a: jax.jit(f)(*a)
    for with_live in (False, True):
        lv = live if with_live else None
        same(
            f"visit_step(live={with_live})",
            run(lambda i, m, q: ops.visit_step(vectors, attrs, lv, i, m, q, lo[0], hi[0]),
                idx[0], mask[0], qs[0]),
            run(lambda i, m, q: ref.visit_step_ref(vectors, attrs, lv, i, m, q, lo[0], hi[0]),
                idx[0], mask[0], qs[0]),
        )
    for metric in ("l2", "ip"):
        same(
            f"filter_distance_batch({metric})",
            run(lambda *z: ops.filter_distance_batch(*z, metric=metric),
                vectors, attrs, idx, mask, qs, lo, hi),
            run(lambda *z: ref.filter_distance_batch_ref(*z, metric),
                vectors, attrs, idx, mask, qs, lo, hi),
        )
    same("pq_score_batch",
         run(ops.pq_score_batch, codes, attrs, idx, mask, luts, lo, hi),
         run(ref.pq_score_batch_ref, codes, attrs, idx, mask, luts, lo, hi), exact=True)
    same("pq_score",
         run(ops.pq_score, codes, attrs, idx[0], mask[0], luts[0], lo[0], hi[0]),
         run(ref.pq_score_ref, codes, attrs, idx[0], mask[0], luts[0], lo[0], hi[0]),
         exact=True)
    cents = vectors[:1024]
    got = run(lambda q, c: ops.ivf_score(q, c), qs, cents)
    want = run(lambda q, c: ref.ivf_score_ref(q, c), qs, cents)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-2)
    log("   ivf_score(nlist=1024): matches its oracle")
    if not rehearsal:
        text = jax.jit(lambda i, m, q: ops.visit_step(
            vectors, attrs, live, i, m, q, lo[0], hi[0])).lower(idx[0], mask[0], qs[0]).compile().as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError("visit_step did not lower to a Mosaic kernel")


def tied_match(got_ids, truth_ids, x, q):
    """Ids equal as sets, or every difference is a distance tie."""
    import numpy as np

    got = [i for i in got_ids if 0 <= i < x.shape[0]]
    want = [i for i in truth_ids if 0 <= i < x.shape[0]]
    if set(got) == set(want):
        return True
    dist = lambda ids: np.sort(((x[ids].astype(np.float64) - q) ** 2).sum(1))
    if len(got) != len(want):
        return False
    return bool(np.allclose(dist(got), dist(want), rtol=1e-5, atol=1e-6))


# -- the one-chip served path -----------------------------------------------------


def serve_one_chip(args, phases, compiles, device):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import predicate as P
    from repro.core.baselines import brute_force, recall
    from repro.core.index import BuildConfig, build_index
    from repro.core.mutable import MutableIndex
    from repro.core.planner import plan as qplan
    from repro.core.quant import QuantConfig, QuantParams, quantize_index
    from repro.compass import CompassParams
    from repro.kernels import autotune
    from repro.kernels import visit_step as vs
    from repro.obs import events as obs_events
    from repro.obs import registry as obs_reg
    from repro.serving.tenancy import CollectionService

    rng = np.random.default_rng(args.seed)
    n = args.rows
    with phases("kernel_parity"):
        kernel_parity(rng, args.cpu_rehearsal)

    with phases("data"):
        x, attrs, queries = make_corpus(rng, n, args.queries)
        kinds, preds = make_predicates(rng, args.queries, n)
    log(f"rows={n} dim={DIM} attrs={N_ATTRS} queries/collection={args.queries} "
        f"mix={ {k: kinds.count(k) for k in sorted(set(kinds))} }")

    nlist = 1 << max(4, round(np.log2(np.sqrt(n))))
    obs_events.EVENTS.clear()
    with phases("build_index"):
        index = build_index(x, attrs, BuildConfig(nlist=nlist, seed=args.seed))
        jax.block_until_ready(index)
    for ev in obs_events.EVENTS.tail(100, kind="index_build_phase"):
        log(f"   build {ev['phase']}: {ev['wall_s']:.3f} s")
    with phases("quantize_index"):
        pq_index = quantize_index(index, QuantConfig(m=PQ_M, seed=args.seed))
        jax.block_until_ready(pq_index)
    with phases("mutable_wrap"):
        mutable = MutableIndex(index, delta_cap=64)
        snap = mutable.snapshot()
    assert_on_device(pq_index, device, "pq_index")
    assert_on_device(snap.index, device, "exact snapshot")
    log(f"nlist={nlist} graph_degree={index.graph.degree} pq_m={PQ_M} "
        f"row_bucket={snap.index.n_records}")

    pm = CompassParams(k=K, ef=64, planner=True, backend="pallas")
    quant = QuantParams(refine_factor=4)
    svc = CollectionService(pm, batch_size=args.batch, max_wait_s=0.0)
    svc.create("exact", mutable, cache_capacity=0)
    svc.create("pq", pq_index, cache_capacity=0, quant=quant)

    def serve(name, qv, pr):
        rids = [svc.submit(name, q, p) for q, p in zip(qv, pr)]
        out = {r.rid: r for r in svc.run_until_idle()}
        return [out[rid] for rid in rids]

    c0, s0 = compiles.count, compiles.seconds
    with phases("serve_warmup"):
        # one batch per term bucket and collection: compiles the programs
        for name in ("exact", "pq"):
            for kind in ("conj", "disj"):
                sel = [i for i, k in enumerate(kinds) if k == kind][: args.batch]
                serve(name, queries[sel], [preds[i] for i in sel])
    log(f"compiles: {compiles.count - c0} backend compiles, "
        f"{compiles.seconds - s0:.3f} s compiling; service compile_count={svc.compile_count}")
    results = {}
    for name in ("exact", "pq"):
        c1 = compiles.count
        with phases(f"serve_{name}"):
            results[name] = serve(name, queries, preds)
        if compiles.count != c1:
            raise AssertionError(f"serving {name} compiled {compiles.count - c1} programs "
                                 "after warm-up")
        wall = phases.wall[f"serve_{name}"]
        log(f"   {name}: {len(results[name])} queries in {wall:.3f} s "
            f"({len(results[name]) / wall:.1f} queries/s, bring-up figure)")

    with phases("brute_force"):
        truth_ids = np.full((args.queries, K), n, np.int64)
        truth_d = np.full((args.queries, K), np.inf, np.float32)
        xj, aj = jnp.asarray(x), jnp.asarray(attrs)
        for tb in sorted({p.n_terms for p in preds}):
            sel = [i for i, p in enumerate(preds) if p.n_terms == tb]
            bf = brute_force(xj, aj, jnp.asarray(queries[sel]),
                             P.stack_predicates([preds[i] for i in sel]), K)
            truth_ids[sel] = np.asarray(bf.ids)
            truth_d[sel] = np.asarray(bf.dists)

    # which queries the planner routes to PREFILTER, by the service's own
    # planning function over the same index, predicate and parameters
    def planned(idx, params):
        modes, totals = np.zeros(args.queries, int), np.zeros(args.queries, int)
        for tb in sorted({p.n_terms for p in preds}):
            sel = [i for i, p in enumerate(preds) if p.n_terms == tb]
            st = P.stack_predicates([preds[i] for i in sel])
            plans = jax.jit(lambda ix, lo, hi: jax.vmap(lambda l, h: qplan.plan_query(
                ix, l, h, params, params.quant is not None))(lo, hi))(idx, st.lo, st.hi)
            modes[sel] = np.asarray(plans.mode)
            totals[sel] = np.asarray(plans.run_total)
        return modes, totals

    wide = dataclasses.replace(pm, quant=quant, ef=pm.ef * quant.refine_factor,
                               k=pm.ef * quant.refine_factor).resolved()
    summary = {}
    # PREFILTER is exact when every candidate run row reaches the exact
    # result queue: always for the exact tier; for PQ when the runs fit the
    # widened stage-one queue that the exact rerank re-scores
    for name, idx, params, width in (
        ("exact", snap.index, pm.resolved(), pm.resolved().prefilter_cap),
        ("pq", pq_index, wide, wide.ef),
    ):
        ids = np.stack([r.ids for r in results[name]])
        r10 = recall(ids, truth_ids, truth_d, n)
        modes, totals = planned(idx, params)
        mix = {qplan.MODE_NAMES[m]: int((modes == m).sum()) for m in range(3)}
        pre = [i for i in range(args.queries)
               if modes[i] == qplan.PREFILTER and totals[i] <= width]
        bad = [i for i in pre if not tied_match(ids[i], truth_ids[i], x, queries[i])]
        summary[name] = {"recall@10": r10, "modes": mix, "prefilter_checked": len(pre)}
        log(f"   {name}: recall@10={r10:.4f} (floor {RECALL_FLOOR[name]}) modes={mix} "
            f"prefilter exact {len(pre) - len(bad)}/{len(pre)}")
        if r10 < RECALL_FLOOR[name]:
            raise AssertionError(f"{name}: recall@10 {r10:.4f} < floor {RECALL_FLOOR[name]}")
        if bad:
            raise AssertionError(f"{name}: PREFILTER ids differ from brute force for "
                                 f"queries {bad[:8]}")
        if not pre:
            raise AssertionError(f"{name}: no query was checked on the PREFILTER path")
    if not any(summary[c]["modes"]["cooperative"] for c in summary):
        raise AssertionError("the mix never reached COOPERATIVE")

    with phases("writes"):
        exact = svc.collection("exact")
        new_gids = list(range(n, n + 3))
        new_vecs = queries[:3] + 0.01
        new_attrs = np.full((3, N_ATTRS), 0.5, np.float32)
        for g, v, a in zip(new_gids, new_vecs, new_attrs):
            exact.submit_upsert(g, v, a)
        replaced = int(results["exact"][3].ids[0])  # an existing row, re-written
        exact.submit_upsert(replaced, queries[3] + 0.01, new_attrs[0])
        victim_q = 4
        victim = int(results["exact"][victim_q].ids[0])
        if victim == replaced:
            victim_q, victim = 5, int(results["exact"][5].ids[0])
        exact.submit_delete(victim)
        around = P.Pred.range(0, 0.49, 0.51).tensor(N_ATTRS)
        reads = serve("exact", np.concatenate([new_vecs, queries[3:4] + 0.01]), [around] * 4)
        for g, r in zip(new_gids + [replaced], reads):
            if int(r.ids[0]) != g:
                raise AssertionError(f"upserted gid {g} not read back (got {r.ids[:3]})")
        again = serve("exact", queries[victim_q:victim_q + 1], [preds[victim_q]])[0]
        if victim in set(int(i) for i in again.ids):
            raise AssertionError(f"deleted gid {victim} surfaced again")
        log(f"   4 upserts read back (3 new, 1 replacing gid {replaced}); "
            f"deleted gid {victim} absent")

    fallbacks = obs_reg.registry().get("compass_kernel_fallback_total")
    if fallbacks is not None and fallbacks.samples():
        raise AssertionError(f"kernel fallbacks: {fallbacks.samples()}")
    if vs.TRACE_COUNT == 0:
        raise AssertionError("the fused visit_step kernel was never traced")
    if not args.cpu_rehearsal:
        for key, exe in svc._executables.items():
            if "tpu_custom_call" not in exe.as_text():
                raise AssertionError(f"served program {key[:3]} has no Mosaic kernel")
    log(f"visit_step traces={vs.TRACE_COUNT}; kernel fallbacks: none")
    for key, dec in autotune.decisions().items():
        log(f"   autotune {key}: {dec}")
    return summary


# -- the four-chip sharded path ---------------------------------------------------


def serve_four_chips(args, phases):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as PS

    from repro.core import predicate as P
    from repro.core.baselines import brute_force, recall
    from repro.core.distributed import build_sharded_index, make_distributed_search
    from repro.core.index import BuildConfig
    from repro.compass import CompassParams
    from repro.kernels import visit_step as vs
    from repro.obs import registry as obs_reg

    rng = np.random.default_rng(args.seed)
    n_shards = 4
    n = args.rows
    with phases("data"):
        x, attrs, queries = make_corpus(rng, n, args.queries)
        kinds, preds = make_predicates(rng, args.queries, n)
    nlist = 1 << max(4, round(np.log2(np.sqrt(n // n_shards))))
    with phases("build_sharded_index"):
        sidx = build_sharded_index(x, attrs, n_shards, BuildConfig(nlist=nlist, seed=args.seed))
        jax.block_until_ready(sidx)
    devices = jax.devices()[:n_shards]
    mesh = jax.sharding.Mesh(np.array(devices), ("shard",))
    sidx = jax.device_put(sidx, NamedSharding(mesh, PS("shard")))
    for path, leaf in jax.tree_util.tree_leaves_with_path(sidx):
        placed = sorted((s.index[0].start, s.device.id) for s in leaf.addressable_shards)
        want = [(i, d.id) for i, d in enumerate(devices)]
        if placed != want:
            raise AssertionError(f"shard leaf {jax.tree_util.keystr(path)} placed {placed}")
    log(f"rows={n} shards={n_shards} rows/shard={n // n_shards} nlist/shard={nlist}; "
        f"every leaf: shard i on device i")

    search = make_distributed_search(mesh, CompassParams(k=K, ef=64, backend="pallas"))
    ids_all = []
    with phases("sharded_search"):
        for tb in sorted({p.n_terms for p in preds}):
            sel = [i for i, p in enumerate(preds) if p.n_terms == tb]
            with jax.set_mesh(mesh):
                ids, _ = search(sidx, jnp.asarray(queries[sel]),
                                P.stack_predicates([preds[i] for i in sel]))
            ids_all.append((sel, np.asarray(ids)))
    with phases("brute_force"):
        truth_ids = np.full((args.queries, K), n, np.int64)
        truth_d = np.full((args.queries, K), np.inf, np.float32)
        got = np.full((args.queries, K), n, np.int64)
        for sel, ids in ids_all:
            bf = brute_force(jnp.asarray(x), jnp.asarray(attrs), jnp.asarray(queries[sel]),
                             P.stack_predicates([preds[i] for i in sel]), K)
            truth_ids[sel], truth_d[sel] = np.asarray(bf.ids), np.asarray(bf.dists)
            got[sel] = ids
    r10 = recall(got, truth_ids, truth_d, n)
    log(f"   sharded recall@10={r10:.4f} (floor {SHARDED_RECALL_FLOOR})")
    if r10 < SHARDED_RECALL_FLOOR:
        raise AssertionError(f"sharded recall@10 {r10:.4f} < {SHARDED_RECALL_FLOOR}")
    fallbacks = obs_reg.registry().get("compass_kernel_fallback_total")
    if fallbacks is not None and fallbacks.samples():
        raise AssertionError(f"kernel fallbacks: {fallbacks.samples()}")
    if vs.TRACE_COUNT == 0:
        raise AssertionError("the fused visit_step kernel was never traced")
    return {"recall@10": r10}


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.cpu_rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if args.chips == 4:
            flags = os.environ.get("XLA_FLAGS", "")
            os.environ["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count=4"
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: no TPU found (platform {dev.platform!r}); "
              "use --cpu-rehearsal for a CPU run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips requested, {len(devices)} found",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.compile_cache import configure as configure_compile_cache
    from repro.obs import registry as obs_reg

    cache_dir = configure_compile_cache()
    obs_reg.set_enabled(True)
    log(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={cache_dir}")
    if args.rows < ROWS:
        why = "CPU rehearsal" if args.cpu_rehearsal else FOUR_CHIP_CUT
        log(f"cut: {args.rows} rows of SIFT1M's {ROWS} ({why})")
    check_kernels_native(args.cpu_rehearsal)
    phases, compiles = Phases(), CompileLog()
    t0 = time.perf_counter()
    if args.chips == 4:
        summary = serve_four_chips(args, phases)
    else:
        summary = serve_one_chip(args, phases, compiles, dev)
    log(f"summary: {json.dumps(summary, sort_keys=True)}")
    log(f"total {time.perf_counter() - t0:.3f} s; backend compiles {compiles.count} "
        f"({compiles.seconds:.3f} s)")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
