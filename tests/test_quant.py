"""Quantized-tier tests (core/quant + kernels/pq_score + engine wiring).

Covers the contracts DESIGN.md §Quantization promises: encode/decode error
bounds, bitwise ADC ref/pallas parity (sentinel-id-under-true-mask
included), rerank exactness at sufficient refine_factor, planner-mode
parity with quantization on, mutable re-encode on compaction, serving
cache-key separation, and the quant=None bitwise-no-op guarantee.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import predicate as P
from repro.core.baselines import brute_force, recall
from repro.core.quant import (
    QuantConfig,
    QuantParams,
    decode_all,
    encode_rows,
    quant_mse,
    quantize_index,
    quantize_vectors,
)
from repro.compass import CompassParams, compass_search
from repro.kernels import ops, ref

K = 10


@pytest.fixture(scope="module")
def quant_index(built_index):
    return quantize_index(built_index, QuantConfig(m=8, iters=6), "l2")


def _pred_batch(tree, a, b):
    return P.stack_predicates([tree.tensor(a)] * b)


WORKLOADS = {
    "conj": P.Pred.and_(P.Pred.range(0, 0.2, 0.7), P.Pred.range(1, 0.1, 0.9)),
    "disj": P.Pred.or_(
        P.Pred.range(0, 0.0, 0.2), P.Pred.range(1, 0.8, 1.0), P.Pred.range(2, 0.4, 0.5)
    ),
    "narrow": P.Pred.and_(P.Pred.range(0, 0.4, 0.5), P.Pred.range(1, 0.3, 0.4)),
}


# ---------------------------------------------------------------------------
# encode / decode
# ---------------------------------------------------------------------------


def test_encode_decode_error_bounds(corpus):
    x, _, _ = corpus
    var = float(np.var(x))
    errs = {}
    for m in (4, 8):
        qv = quantize_vectors(x, QuantConfig(m=m, iters=6))
        assert qv.codes.shape == (x.shape[0] + 1, m) and qv.codes.dtype == jnp.uint8
        dec = np.asarray(decode_all(qv))
        assert dec.shape == x.shape
        mse = float(np.mean((dec - x) ** 2))
        errs[m] = mse
        # quantization error must be well below the data's own variance,
        # and the recorded train_mse must be the real figure
        assert mse < 0.5 * var
        np.testing.assert_allclose(float(qv.train_mse), mse, rtol=1e-5)
        np.testing.assert_allclose(quant_mse(qv, x), mse, rtol=1e-5)
    # more subspaces -> finer quantization
    assert errs[8] < errs[4]


def test_encode_pads_odd_dims():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(500, 17)).astype(np.float32)  # 17 % 4 != 0
    qv = quantize_vectors(x, QuantConfig(m=4, iters=4))
    assert qv.dsub == 5  # ceil(17/4)
    dec = np.asarray(decode_all(qv))
    assert dec.shape == x.shape
    assert float(np.mean((dec - x) ** 2)) < float(np.var(x))


def test_quant_config_validation():
    with pytest.raises(ValueError):
        QuantConfig(ks=512)  # uint8 overflow
    with pytest.raises(ValueError):
        QuantConfig(residual=True).resolve_residual("ip")
    assert QuantConfig().resolve_residual("l2") is True
    assert QuantConfig().resolve_residual("ip") is False
    with pytest.raises(ValueError):
        QuantParams(refine_factor=0)
    with pytest.raises(ValueError):
        QuantParams(rerank="fast")


def test_bytes_per_vector_compression(quant_index):
    d = quant_index.dim
    bpv = quant_index.qvecs.bytes_per_vector
    assert bpv >= quant_index.qvecs.m  # codes alone
    assert 4.0 * d / bpv >= 2.0  # honest (codebook-amortized) compression


# ---------------------------------------------------------------------------
# kernel parity (ref oracle vs pallas interpret) — bitwise
# ---------------------------------------------------------------------------


def _mk_pq(rng, n, m, ks, dsub, a):
    codes = np.concatenate(
        [rng.integers(0, ks, (n, m)), np.zeros((1, m))], 0
    ).astype(np.uint8)
    attrs = np.concatenate(
        [rng.uniform(size=(n, a)), np.full((1, a), np.inf)], 0
    ).astype(np.float32)
    cb = rng.normal(size=(m, ks, dsub)).astype(np.float32)
    return jnp.asarray(codes), jnp.asarray(attrs), jnp.asarray(cb)


@pytest.mark.parametrize("n,m,ks,dsub,a,t,v", [
    (50, 4, 16, 3, 2, 1, 16),
    (200, 8, 256, 4, 4, 4, 33),   # full uint8 range, non-multiple V
    (100, 16, 64, 5, 3, 2, 8),
])
def test_pq_score_matches_ref_bitwise(n, m, ks, dsub, a, t, v):
    rng = np.random.default_rng(0)
    codes, attrs, cb = _mk_pq(rng, n, m, ks, dsub, a)
    idx = jnp.asarray(rng.integers(0, n + 1, v).astype(np.int32))
    mask = jnp.asarray(rng.uniform(size=v) > 0.3)
    q = jnp.asarray(rng.normal(size=m * dsub).astype(np.float32))
    lo = jnp.asarray(rng.uniform(0, 0.5, (t, a)).astype(np.float32))
    hi = jnp.asarray(rng.uniform(0.5, 1.0, (t, a)).astype(np.float32))
    # both sides look up the one (m, ks) table built once per query, so
    # parity is bitwise by construction
    lut = ref.adc_lut(cb, q)
    d_k, p_k = jax.jit(lambda *z: ops.pq_score(*z))(codes, attrs, idx, mask, lut, lo, hi)
    d_r, p_r = jax.jit(lambda *z: ref.pq_score_ref(*z))(codes, attrs, idx, mask, lut, lo, hi)
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))


@pytest.mark.parametrize("b,n,m,ks,dsub,a,t,v", [
    (1, 50, 4, 16, 3, 2, 1, 16),
    (4, 200, 8, 256, 4, 4, 4, 33),
    (3, 100, 16, 64, 5, 3, 2, 8),
])
def test_pq_score_batch_matches_ref_bitwise(b, n, m, ks, dsub, a, t, v):
    rng = np.random.default_rng(1)
    codes, attrs, cb = _mk_pq(rng, n, m, ks, dsub, a)
    idx = jnp.asarray(rng.integers(0, n + 1, (b, v)).astype(np.int32))
    mask = jnp.asarray(rng.uniform(size=(b, v)) > 0.3)
    q = jnp.asarray(rng.normal(size=(b, m * dsub)).astype(np.float32))
    lo = jnp.asarray(rng.uniform(0, 0.5, (b, t, a)).astype(np.float32))
    hi = jnp.asarray(rng.uniform(0.5, 1.0, (b, t, a)).astype(np.float32))
    luts = jax.vmap(lambda qr: ref.adc_lut(cb, qr))(q)
    d_k, p_k = jax.jit(lambda *z: ops.pq_score_batch(*z))(
        codes, attrs, idx, mask, luts, lo, hi
    )
    d_r, p_r = jax.jit(lambda *z: ref.pq_score_batch_ref(*z))(
        codes, attrs, idx, mask, luts, lo, hi
    )
    assert d_k.shape == (b, v) and p_k.shape == (b, v)
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))


def test_pq_score_sentinel_under_true_mask():
    """A sentinel id is a masked-out visit even when the mask bit is true —
    the same validity rule as filter_distance (dist +inf, passed False)."""
    rng = np.random.default_rng(2)
    n, m, ks, dsub, a = 30, 4, 8, 2, 2
    codes, attrs, cb = _mk_pq(rng, n, m, ks, dsub, a)
    idx = jnp.asarray(np.array([0, n, 5, n], np.int32))  # two sentinels
    mask = jnp.asarray(np.array([True, True, True, True]))
    q = jnp.asarray(rng.normal(size=m * dsub).astype(np.float32))
    lut = ref.adc_lut(cb, q)
    lo = jnp.full((1, a), -np.inf, jnp.float32)  # vacuous bounds: all pass
    hi = jnp.full((1, a), np.inf, jnp.float32)
    for use_pallas in (False, True):
        d, p = jax.jit(
            lambda *z: ops.pq_score(*z, use_pallas=use_pallas)
        )(codes, attrs, idx, mask, lut, lo, hi)
        d, p = np.asarray(d), np.asarray(p)
        assert np.isinf(d[1]) and np.isinf(d[3])
        assert not p[1] and not p[3]
        assert np.isfinite(d[0]) and np.isfinite(d[2])
        assert p[0] and p[2]


# ---------------------------------------------------------------------------
# two-stage search: rerank exactness + counters
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workload", sorted(WORKLOADS))
def test_rerank_matches_exact_search(corpus, built_index, quant_index, workload):
    """With refine_factor high enough, the quantized top-k recovers the
    exact engine's top-k (the rerank contract)."""
    x, attrs, queries = corpus
    n = x.shape[0]
    pred = _pred_batch(WORKLOADS[workload], attrs.shape[1], len(queries))
    qj = jnp.asarray(queries)
    pm = CompassParams(k=K, ef=64, backend="ref")
    exact = compass_search(built_index, qj, pred, pm)
    quant = compass_search(
        quant_index, qj, pred,
        dataclasses.replace(pm, quant=QuantParams(refine_factor=4)),
    )
    r = recall(
        np.asarray(quant.ids), np.asarray(exact.ids), np.asarray(exact.dists), n
    )
    assert r >= 0.95, f"quantized vs exact recall {r} on {workload}"
    # reranked distances are true full-precision distances
    truth = brute_force(jnp.asarray(x), jnp.asarray(attrs), qj, pred, K)
    ids_q, d_q = np.asarray(quant.ids), np.asarray(quant.dists)
    for lane in range(len(queries)):
        fin = np.isfinite(d_q[lane])
        diff = x[ids_q[lane][fin]] - queries[lane][None, :]
        np.testing.assert_allclose(
            d_q[lane][fin], np.sum(diff * diff, axis=1), rtol=1e-4
        )


def test_refine_factor_monotone_recall(corpus, quant_index):
    """Against brute-force ground truth (not the exact engine's ef-bounded
    run, which a wider stage one can legitimately *beat*, making overlap
    non-monotone), more refine means more recall."""
    x, attrs, queries = corpus
    n = x.shape[0]
    pred = _pred_batch(WORKLOADS["conj"], attrs.shape[1], len(queries))
    qj = jnp.asarray(queries)
    truth = brute_force(jnp.asarray(x), jnp.asarray(attrs), qj, pred, K)
    pm = CompassParams(k=K, ef=32, backend="ref")
    rs = []
    for rf in (1, 4):
        res = compass_search(
            quant_index, qj, pred, dataclasses.replace(pm, quant=QuantParams(refine_factor=rf))
        )
        rs.append(
            recall(np.asarray(res.ids), np.asarray(truth.ids), np.asarray(truth.dists), n)
        )
    assert rs[1] >= rs[0]


def test_quant_counters(corpus, quant_index):
    x, attrs, queries = corpus
    pred = _pred_batch(WORKLOADS["conj"], attrs.shape[1], len(queries))
    qj = jnp.asarray(queries)
    pm = CompassParams(k=K, ef=32, backend="ref")
    res = compass_search(quant_index, qj, pred, pm)  # quant off
    assert np.all(np.asarray(res.stats.n_adc) == 0)
    assert np.all(np.asarray(res.stats.n_rerank) == 0)
    resq = compass_search(
        quant_index, qj, pred, dataclasses.replace(pm, quant=QuantParams(refine_factor=2))
    )
    assert np.all(np.asarray(resq.stats.n_adc) > 0)
    # rerank touched exactly the live stage-one survivors, and those exact
    # reads are counted in the full-precision #Comp figure too
    nr = np.asarray(resq.stats.n_rerank)
    assert np.all(nr > 0) and np.all(nr <= 2 * 32)
    assert np.all(np.asarray(resq.stats.n_dist) >= nr)


def test_rerank_modes_run(corpus, quant_index):
    x, attrs, queries = corpus
    pred = _pred_batch(WORKLOADS["conj"], attrs.shape[1], len(queries))
    qj = jnp.asarray(queries)
    base = CompassParams(k=K, ef=32, backend="ref")
    res_full = compass_search(
        quant_index, qj, pred, dataclasses.replace(base, quant=QuantParams(2, "full"))
    )
    res_dec = compass_search(
        quant_index, qj, pred, dataclasses.replace(base, quant=QuantParams(2, "decode"))
    )
    res_none = compass_search(
        quant_index, qj, pred, dataclasses.replace(base, quant=QuantParams(2, "none"))
    )
    for res in (res_full, res_dec, res_none):
        assert res.ids.shape == (len(queries), K)
    # "none" skips stage two entirely
    assert np.all(np.asarray(res_none.stats.n_rerank) == 0)
    assert np.all(np.asarray(res_dec.stats.n_rerank) > 0)
    # decode-mode distances are ADC-equal (summation order aside), so the
    # top-1 candidate should broadly agree with the full rerank
    agree = np.mean(
        np.asarray(res_dec.ids)[:, 0] == np.asarray(res_full.ids)[:, 0]
    )
    assert agree >= 0.5


# ---------------------------------------------------------------------------
# engine integration: quant=None no-op, backend parity, planner parity
# ---------------------------------------------------------------------------


def test_quant_none_bitwise_unchanged(corpus, built_index, quant_index):
    """Attaching codes to an index must not move a single bit of exact
    search — the qvecs branch is trace-time (pytree-structural)."""
    x, attrs, queries = corpus
    qj = jnp.asarray(queries)
    for workload, tree in sorted(WORKLOADS.items()):
        pred = _pred_batch(tree, attrs.shape[1], len(queries))
        for pm in (
            CompassParams(k=K, ef=48, backend="ref"),
            CompassParams(k=K, ef=48, backend="ref", planner=True),
            CompassParams(k=K, ef=48, backend="pallas"),
        ):
            plain = compass_search(built_index, qj, pred, pm)
            carried = compass_search(quant_index, qj, pred, pm)
            np.testing.assert_array_equal(
                np.asarray(plain.ids), np.asarray(carried.ids), err_msg=workload
            )
            np.testing.assert_array_equal(
                np.asarray(plain.dists), np.asarray(carried.dists), err_msg=workload
            )


def test_quant_backend_parity(corpus, quant_index):
    """ref and pallas backends agree bitwise on the quantized path (the
    pq_score kernel looks up the same per-query table as the jnp path, and
    the rerank scan is the existing filter_distance parity surface)."""
    x, attrs, queries = corpus
    qj = jnp.asarray(queries)
    for workload, tree in sorted(WORKLOADS.items()):
        pred = _pred_batch(tree, attrs.shape[1], len(queries))
        for planner in (False, True):
            pm = CompassParams(
                k=K, ef=48, planner=planner, quant=QuantParams(refine_factor=2)
            )
            r_ref = compass_search(
                quant_index, qj, pred, dataclasses.replace(pm, backend="ref")
            )
            r_pal = compass_search(
                quant_index, qj, pred, dataclasses.replace(pm, backend="pallas")
            )
            np.testing.assert_array_equal(
                np.asarray(r_ref.ids), np.asarray(r_pal.ids),
                err_msg=f"{workload} planner={planner}",
            )
            np.testing.assert_array_equal(
                np.asarray(r_ref.dists), np.asarray(r_pal.dists),
                err_msg=f"{workload} planner={planner}",
            )


def test_planner_modes_with_quant(corpus, quant_index):
    """The planner keeps planning under quantization: a narrow predicate
    goes PREFILTER and (ADC scan + exact rerank) still recovers the exact
    engine's answer; work lands in n_adc, not n_dist."""
    x, attrs, queries = corpus
    n = x.shape[0]
    qj = jnp.asarray(queries)
    pred = _pred_batch(WORKLOADS["narrow"], attrs.shape[1], len(queries))
    pm = CompassParams(k=K, ef=48, backend="ref", planner=True,
                       quant=QuantParams(refine_factor=4))
    res = compass_search(quant_index, qj, pred, pm)
    from repro.core.planner.plan import PREFILTER

    assert np.all(np.asarray(res.stats.mode) == PREFILTER)
    assert np.all(np.asarray(res.stats.n_adc) > 0)
    truth = brute_force(jnp.asarray(x), jnp.asarray(attrs), qj, pred, K)
    r = recall(np.asarray(res.ids), np.asarray(truth.ids), np.asarray(truth.dists), n)
    assert r == 1.0  # PREFILTER materializes every match; rerank is exact
    # planner-on and planner-off agree on the quantized result set
    res_off = compass_search(
        quant_index, qj, pred, dataclasses.replace(pm, planner=False)
    )
    r_par = recall(
        np.asarray(res.ids), np.asarray(res_off.ids), np.asarray(res_off.dists), n
    )
    assert r_par >= 0.95


def test_quant_requires_quantized_index(built_index, corpus):
    x, attrs, queries = corpus
    pred = _pred_batch(WORKLOADS["conj"], attrs.shape[1], len(queries))
    with pytest.raises(ValueError, match="quantized index"):
        compass_search(
            built_index, jnp.asarray(queries), pred,
            CompassParams(k=K, quant=QuantParams()),
        )


# ---------------------------------------------------------------------------
# mutable: delta encoding, re-encode on compaction, retrain
# ---------------------------------------------------------------------------


@pytest.fixture()
def mutable_quant(corpus):
    from repro.core.index import BuildConfig, build_index
    from repro.core.mutable import MutableIndex

    x, attrs, _ = corpus
    cfg = BuildConfig(m=12, nlist=16)
    base = quantize_index(build_index(x[:3000], attrs[:3000], cfg), QuantConfig(m=8, iters=5))
    return MutableIndex(base, delta_cap=64, cfg=cfg)


def test_mutable_delta_scored_quantized(corpus, mutable_quant):
    x, attrs, queries = corpus
    a = attrs.shape[1]
    pm = CompassParams(k=K, ef=32, backend="ref", quant=QuantParams(refine_factor=4))
    gid = 9_000_000
    mutable_quant.upsert(gid, queries[0], np.float32([0.5] * a))
    snap = mutable_quant.snapshot()
    assert snap.delta.qvecs is not None
    # delta codes are the base codebooks' encoding of the delta rows
    want = np.asarray(
        encode_rows(
            snap.index.qvecs.codebooks, snap.index.qvecs.mean, queries[:1]
        )
    )
    np.testing.assert_array_equal(np.asarray(snap.delta.qvecs.codes)[0], want[0])
    pred = _pred_batch(P.Pred.range(0, 0.0, 1.0), a, 1)
    res = mutable_quant.search(queries[:1], pred, pm)
    assert np.asarray(res.ids)[0][0] == gid  # exact-match vector wins top-1
    assert np.all(np.asarray(res.stats.n_adc) > 0)
    assert np.all(np.asarray(res.stats.n_rerank) > 0)


def test_mutable_reencode_on_compaction(corpus, mutable_quant):
    x, attrs, queries = corpus
    a = attrs.shape[1]
    gid = 9_000_001
    mutable_quant.upsert(gid, queries[1], np.float32([0.5] * a))
    old_cb = np.asarray(mutable_quant.base.qvecs.codebooks)
    mutable_quant.compact()
    qv = mutable_quant.base.qvecs
    assert qv is not None, "quantized tier lost in the fold"
    # frozen codebooks carried over; the folded row's code is a fresh
    # encoding of its vector against them
    np.testing.assert_array_equal(np.asarray(qv.codebooks), old_cb)
    pos = int(np.where(mutable_quant.gids == gid)[0][0])
    want = np.asarray(encode_rows(qv.codebooks, qv.mean, queries[1:2]))[0]
    np.testing.assert_array_equal(np.asarray(qv.codes)[pos], want)
    assert len(mutable_quant.quant_drift_log) == 1
    # search still quantized after the fold
    pm = CompassParams(k=K, ef=32, backend="ref", quant=QuantParams(refine_factor=4))
    pred = _pred_batch(P.Pred.range(0, 0.0, 1.0), a, 1)
    res = mutable_quant.search(queries[1:2], pred, pm)
    assert np.asarray(res.ids)[0][0] == gid


def test_mutable_retrain_on_explicit_compact(corpus, mutable_quant):
    x, attrs, queries = corpus
    a = attrs.shape[1]
    mutable_quant.upsert(9_000_002, queries[2], np.float32([0.5] * a))
    old_cb = np.asarray(mutable_quant.base.qvecs.codebooks)
    mutable_quant.compact(retrain_codebooks=True)
    new_cb = np.asarray(mutable_quant.base.qvecs.codebooks)
    assert new_cb.shape == old_cb.shape
    assert not np.array_equal(new_cb, old_cb)  # actually retrained
    assert len(mutable_quant.quant_drift_log) == 1


def test_distributed_mutable_aggregates_quant_counters(corpus):
    from repro.core.distributed import DistributedMutableIndex
    from repro.core.index import BuildConfig, build_index
    from repro.core.mutable import MutableIndex

    x, attrs, queries = corpus
    a = attrs.shape[1]
    cfg = BuildConfig(m=8, nlist=8)
    shards = []
    for s in range(2):
        sl = slice(s * 1000, (s + 1) * 1000)
        base = quantize_index(build_index(x[sl], attrs[sl], cfg), QuantConfig(m=8, iters=4))
        shards.append(
            MutableIndex(
                base, delta_cap=16, cfg=cfg,
                gids=np.arange(sl.start, sl.stop, dtype=np.int64),
            )
        )
    dmi = DistributedMutableIndex(shards)
    pm = CompassParams(k=K, ef=32, backend="ref", quant=QuantParams(refine_factor=2))
    pred = _pred_batch(WORKLOADS["conj"], a, 4)
    res = dmi.search(jnp.asarray(queries[:4]), pred, pm)
    per_shard = [
        sh.search(jnp.asarray(queries[:4]), pred, pm) for sh in dmi.shards
    ]
    np.testing.assert_array_equal(
        np.asarray(res.stats.n_adc),
        sum(np.asarray(p.stats.n_adc) for p in per_shard),
    )
    np.testing.assert_array_equal(
        np.asarray(res.stats.n_rerank),
        sum(np.asarray(p.stats.n_rerank) for p in per_shard),
    )


# ---------------------------------------------------------------------------
# serving: cache-key separation
# ---------------------------------------------------------------------------


def test_serving_cache_key_separation(corpus, quant_index):
    from repro.serving.search_service import SearchService

    x, attrs, queries = corpus
    a = attrs.shape[1]
    tree = WORKLOADS["conj"]
    pm_exact = CompassParams(k=K, ef=32, backend="ref")
    pm_quant = dataclasses.replace(pm_exact, quant=QuantParams(refine_factor=2))
    # the quant config is part of the frozen CompassParams, so the
    # executable cache key separates quantized from exact automatically
    assert pm_exact != pm_quant and hash(pm_exact) != hash(pm_quant)
    svc_q = SearchService(quant_index, pm_quant, batch_size=2, max_wait_s=0.0)
    svc_e = SearchService(quant_index, pm_exact, batch_size=2, max_wait_s=0.0)
    for svc in (svc_q, svc_e):
        svc.submit(queries[0], tree)
        svc.submit(queries[1], tree)
        out = svc.run_until_idle()
        assert len(out) == 2
    assert svc_q.compile_count == 1 and svc_e.compile_count == 1
    sq, se = svc_q.stats(), svc_e.stats()
    assert sq["quant"] == {"refine_factor": 2, "rerank": "full"}
    assert se["quant"] is None
    assert sq["bytes_per_vector"] < se["bytes_per_vector"]
    # quantized service response equals the direct quantized call
    direct = compass_search(
        quant_index,
        jnp.asarray(queries[:1]),
        _pred_batch(tree, a, 1),
        pm_quant,
    )
    svc_q.submit(queries[0], tree)
    (r,) = svc_q.flush()
    np.testing.assert_array_equal(r.ids, np.asarray(direct.ids)[0])
    np.testing.assert_array_equal(r.dists, np.asarray(direct.dists)[0])


def test_serving_rejects_quant_params_without_codes(built_index):
    from repro.serving.search_service import SearchService

    with pytest.raises(ValueError, match="quantized index"):
        SearchService(built_index, CompassParams(k=K, quant=QuantParams()))
