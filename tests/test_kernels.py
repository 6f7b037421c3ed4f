"""Per-kernel validation: shape/dtype sweeps, interpret-mode Pallas vs the
pure-jnp oracle in ref.py, plus hypothesis property tests."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref


def _mk_corpus(rng, n, d, a):
    vectors = rng.normal(size=(n + 1, d)).astype(np.float32)
    attrs = rng.uniform(size=(n + 1, a)).astype(np.float32)
    attrs[-1] = np.inf  # sentinel row
    return jnp.asarray(vectors), jnp.asarray(attrs)


@pytest.mark.parametrize("n,d,a,t,v", [
    (50, 8, 2, 1, 16),
    (200, 32, 4, 4, 33),   # non-multiple V
    (100, 17, 3, 2, 8),    # odd dim
])
def test_filter_distance_matches_ref(n, d, a, t, v):
    rng = np.random.default_rng(0)
    vectors, attrs = _mk_corpus(rng, n, d, a)
    idx = jnp.asarray(rng.integers(0, n + 1, v).astype(np.int32))
    mask = jnp.asarray(rng.uniform(size=v) > 0.3)
    q = jnp.asarray(rng.normal(size=d).astype(np.float32))
    lo = jnp.asarray(rng.uniform(0, 0.5, (t, a)).astype(np.float32))
    hi = jnp.asarray(rng.uniform(0.5, 1.0, (t, a)).astype(np.float32))
    d_k, p_k = ops.filter_distance(vectors, attrs, idx, mask, q, lo, hi)
    d_r, p_r = ref.filter_distance_ref(vectors, attrs, idx, mask, q, lo, hi)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))


@pytest.mark.parametrize("b,n,d,a,t,v", [
    (1, 50, 8, 2, 1, 16),
    (4, 200, 32, 4, 4, 33),   # non-multiple V
    (3, 100, 17, 3, 2, 8),    # odd dim
    (2, 60, 960, 4, 4, 21),   # GIST's width: rows gathered by XLA
])
def test_filter_distance_batch_matches_ref(b, n, d, a, t, v):
    """The planner's batched run-scan entry point: per-lane queries and
    bounds, grid (B, V) — against the vmapped single-query oracle."""
    rng = np.random.default_rng(1)
    vectors, attrs = _mk_corpus(rng, n, d, a)
    idx = jnp.asarray(rng.integers(0, n + 1, (b, v)).astype(np.int32))
    mask = jnp.asarray(rng.uniform(size=(b, v)) > 0.3)
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    lo = jnp.asarray(rng.uniform(0, 0.5, (b, t, a)).astype(np.float32))
    hi = jnp.asarray(rng.uniform(0.5, 1.0, (b, t, a)).astype(np.float32))
    d_k, p_k = ops.filter_distance_batch(vectors, attrs, idx, mask, q, lo, hi)
    d_r, p_r = ref.filter_distance_batch_ref(vectors, attrs, idx, mask, q, lo, hi)
    assert d_k.shape == (b, v) and p_k.shape == (b, v)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))


@pytest.mark.parametrize("b,c,d,dtype", [
    (4, 100, 32, jnp.float32),
    (3, 257, 48, jnp.float32),   # non-multiples of block
    (8, 64, 130, jnp.bfloat16),  # odd feature dim + bf16
])
def test_ivf_score_matches_ref(b, c, d, dtype):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(b, d))).astype(dtype)
    cent = jnp.asarray(rng.normal(size=(c, d))).astype(dtype)
    got = ops.ivf_score(q, cent, bb=2, bc=64, bd=32)
    want = ref.ivf_score_ref(q, cent)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


@pytest.mark.parametrize("b,s,h,kv,dh,dtype", [
    (2, 128, 4, 4, 32, jnp.float32),
    (1, 200, 8, 2, 64, jnp.float32),   # GQA + ragged seq
    (2, 96, 4, 1, 16, jnp.bfloat16),   # MQA + bf16
])
def test_flash_attention_matches_ref(b, s, h, kv, dh, dtype):
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)) * 0.5).astype(dtype)
    k = jnp.asarray(rng.normal(size=(b, s, kv, dh)) * 0.5).astype(dtype)
    v = jnp.asarray(rng.normal(size=(b, s, kv, dh)) * 0.5).astype(dtype)
    got = ops.flash_attention(q, k, v, bq=64, bk=64)
    want = ref.flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


@settings(max_examples=10, deadline=None)
@given(
    v=st.integers(1, 40),
    d=st.integers(2, 24),
    seed=st.integers(0, 100),
)
def test_property_filter_distance(v, d, seed):
    """Masked entries are +inf/false; unmasked distances are exact."""
    rng = np.random.default_rng(seed)
    n, a, t = 30, 2, 2
    vectors, attrs = _mk_corpus(rng, n, d, a)
    idx = jnp.asarray(rng.integers(0, n, v).astype(np.int32))
    mask = jnp.asarray(rng.uniform(size=v) > 0.5)
    q = jnp.asarray(rng.normal(size=d).astype(np.float32))
    lo = jnp.zeros((t, a), jnp.float32)
    hi = jnp.ones((t, a), jnp.float32)
    d_k, p_k = ops.filter_distance(vectors, attrs, idx, mask, q, lo, hi)
    m = np.asarray(mask)
    assert np.all(np.isinf(np.asarray(d_k)[~m]))
    assert not np.any(np.asarray(p_k)[~m])
    want = ((np.asarray(vectors)[np.asarray(idx)[m]] - np.asarray(q)) ** 2).sum(-1)
    np.testing.assert_allclose(np.asarray(d_k)[m], want, rtol=1e-5)


@settings(max_examples=8, deadline=None)
@given(s=st.integers(3, 80), seed=st.integers(0, 50))
def test_property_flash_attention_row_stochastic(s, seed):
    """Causality: output at position 0 equals v[0] exactly (only itself
    visible); all outputs are finite."""
    rng = np.random.default_rng(seed)
    b, h, dh = 1, 2, 8
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32))
    out = ops.flash_attention(q, k, v, bq=32, bk=32)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(
        np.asarray(out[:, 0]), np.asarray(v[:, 0]), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# Metric sweep: every scoring kernel implements "ip" alongside "l2", sharing
# the ref path's per-row expression (kernels.ref.row_distance / adc_lut), so
# parity is *bitwise* — but only inside one compile context: XLA may fuse the
# eager oracle differently, so both sides go through jax.jit before compare
# (the discipline test_quant.py established for the LUT chain).
# ---------------------------------------------------------------------------


def _both_jitted(kernel_fn, ref_fn, *args):
    got = jax.jit(lambda *z: kernel_fn(*z))(*args)
    want = jax.jit(lambda *z: ref_fn(*z))(*args)
    return got, want


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_filter_distance_metric_parity(metric):
    rng = np.random.default_rng(21)
    n, d, a, t, v = 120, 24, 3, 2, 33
    vectors, attrs = _mk_corpus(rng, n, d, a)
    idx = jnp.asarray(rng.integers(0, n + 1, v).astype(np.int32))
    mask = jnp.asarray(rng.uniform(size=v) > 0.3)
    q = jnp.asarray(rng.normal(size=d).astype(np.float32))
    lo = jnp.asarray(rng.uniform(0, 0.5, (t, a)).astype(np.float32))
    hi = jnp.asarray(rng.uniform(0.5, 1.0, (t, a)).astype(np.float32))
    (d_k, p_k), (d_r, p_r) = _both_jitted(
        lambda *z: ops.filter_distance(*z, metric=metric),
        lambda *z: ref.filter_distance_ref(*z, metric),
        vectors, attrs, idx, mask, q, lo, hi,
    )
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_ivf_score_ip_matches_ref(metric):
    rng = np.random.default_rng(22)
    b, c, d = 5, 130, 40
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    cent = jnp.asarray(rng.normal(size=(c, d)).astype(np.float32))
    got = ops.ivf_score(q, cent, metric=metric, bb=2, bc=64, bd=32)
    want = ref.ivf_score_ref(q, cent, metric)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_pq_score_metric_parity(metric):
    rng = np.random.default_rng(23)
    n, a, t, v = 90, 3, 2, 17
    m, ks, dsub = 4, 16, 4
    _, attrs = _mk_corpus(rng, n, 8, a)
    codes = jnp.asarray(
        np.concatenate(
            [rng.integers(0, ks, size=(n, m)), np.zeros((1, m), np.int64)]
        ).astype(np.uint8)
    )
    codebooks = jnp.asarray(rng.normal(size=(m, ks, dsub)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, n + 1, v).astype(np.int32))
    mask = jnp.asarray(rng.uniform(size=v) > 0.3)
    qr = jnp.asarray(rng.normal(size=m * dsub).astype(np.float32))
    lo = jnp.asarray(rng.uniform(0, 0.5, (t, a)).astype(np.float32))
    hi = jnp.asarray(rng.uniform(0.5, 1.0, (t, a)).astype(np.float32))
    # the table carries the metric and is built once per query, outside
    # both paths: kernel and oracle look up the same values and fold them
    # in the same order, so parity is bitwise by construction
    lut = jax.jit(lambda c, q: ref.adc_lut(c, q, metric))(codebooks, qr)
    (d_k, p_k), (d_r, p_r) = _both_jitted(
        ops.pq_score, ref.pq_score_ref, codes, attrs, idx, mask, lut, lo, hi,
    )
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))


# ---------------------------------------------------------------------------
# Fused visit-step kernel: one pallas_call for gather + distance + predicate
# + tombstone + admission.  rows_per_step blocking must never change the
# math (rows are independent), so parity is asserted across rb values,
# metrics, live/no-live, and under vmap (how the engine calls it).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("with_live", [False, True])
@pytest.mark.parametrize("rb", [1, 3, None])
def test_visit_step_matches_ref(metric, with_live, rb):
    rng = np.random.default_rng(31)
    n, d, a, t, v = 150, 19, 3, 2, 29  # odd dim, V not a multiple of rb
    vectors, attrs = _mk_corpus(rng, n, d, a)
    live = jnp.asarray(rng.uniform(size=n + 1) > 0.2) if with_live else None
    idx = jnp.asarray(rng.integers(0, n + 1, v).astype(np.int32))
    mask = jnp.asarray(rng.uniform(size=v) > 0.3)
    q = jnp.asarray(rng.normal(size=d).astype(np.float32))
    lo = jnp.asarray(rng.uniform(0, 0.5, (t, a)).astype(np.float32))
    hi = jnp.asarray(rng.uniform(0.5, 1.0, (t, a)).astype(np.float32))
    kw = {} if rb is None else {"rows_per_step": rb}
    (d_k, ad_k), (d_r, ad_r) = _both_jitted(
        lambda *z: ops.visit_step(*z, metric=metric, **kw),
        lambda *z: ref.visit_step_ref(*z, metric),
        vectors, attrs, live, idx, mask, q, lo, hi,
    )
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))
    np.testing.assert_array_equal(np.asarray(ad_k), np.asarray(ad_r))
    # admission semantics: admit is either the distance or +inf, and is +inf
    # wherever the row is masked out
    ad = np.asarray(ad_k)
    dk = np.asarray(d_k)
    assert np.all(np.isinf(ad) | (ad == dk))
    assert np.all(np.isinf(ad[~np.asarray(mask)]))


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("kernel", ["visit_step", "visit_step_live", "filter_distance_batch"])
def test_dma_route_matches_ref(kernel, metric):
    """At a lane-aligned width the float32 rows are DMA'd from HBM rather
    than gathered by XLA (the odd widths above); parity stays bitwise."""
    from repro.kernels.row_gather import dma_rows

    rng = np.random.default_rng(35)
    b, n, d, a, t, v = 2, 60, 128, 3, 2, 21
    assert dma_rows(metric, d) and not dma_rows(metric, 19)
    vectors, attrs = _mk_corpus(rng, n, d, a)
    live = jnp.asarray(rng.uniform(size=n + 1) > 0.2)
    idx = jnp.asarray(rng.integers(0, n + 1, (b, v)).astype(np.int32))
    mask = jnp.asarray(rng.uniform(size=(b, v)) > 0.3)
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    lo = jnp.asarray(rng.uniform(0, 0.5, (b, t, a)).astype(np.float32))
    hi = jnp.asarray(rng.uniform(0.5, 1.0, (b, t, a)).astype(np.float32))
    if kernel == "filter_distance_batch":
        got, want = _both_jitted(
            lambda *z: ops.filter_distance_batch(*z, metric=metric),
            lambda *z: ref.filter_distance_batch_ref(*z, metric),
            vectors, attrs, idx, mask, q, lo, hi,
        )
    else:
        lv = live if kernel == "visit_step_live" else None
        got, want = _both_jitted(
            lambda *z: ops.visit_step(*z, metric=metric, rows_per_step=8),
            lambda *z: ref.visit_step_ref(*z, metric),
            vectors, attrs, lv, idx[0], mask[0], q[0], lo[0], hi[0],
        )
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("with_live", [False, True])
def test_wide_visit_step_matches_ref(with_live):
    """At GIST's 960-d the rows are not DMA'd (960 is not a multiple of
    128) but gathered by XLA; parity stays bitwise, tombstones or not."""
    from repro.kernels.row_gather import dma_rows

    rng = np.random.default_rng(36)
    n, d, a, t, v = 60, 960, 4, 4, 21
    assert not dma_rows("l2", d)
    vectors, attrs = _mk_corpus(rng, n, d, a)
    live = jnp.asarray(rng.uniform(size=n + 1) > 0.2) if with_live else None
    idx = jnp.asarray(rng.integers(0, n + 1, v).astype(np.int32))
    mask = jnp.asarray(rng.uniform(size=v) > 0.3)
    q = jnp.asarray(rng.normal(size=d).astype(np.float32))
    lo = jnp.asarray(rng.uniform(0, 0.5, (t, a)).astype(np.float32))
    hi = jnp.asarray(rng.uniform(0.5, 1.0, (t, a)).astype(np.float32))
    got, want = _both_jitted(
        lambda *z: ops.visit_step(*z, metric="l2"),
        lambda *z: ref.visit_step_ref(*z, "l2"),
        vectors, attrs, live, idx, mask, q, lo, hi,
    )
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("score,width,scoped", [
    ("l2", 960, True), ("ip", 48, True), ("l2", 128, False), ("adc", 16, False)])
def test_xla_route_gather_has_its_own_scope(score, width, scoped):
    """Only float32 rows that cannot be DMA'd are gathered under
    ``compass/row_gather/xla``: not lane-aligned rows (DMA'd), not PQ codes."""
    from repro.kernels.row_gather import gather_score

    b, n, a, t, v = 2, 40, 3, 2, 16
    dtype = jnp.uint8 if score == "adc" else jnp.float32
    lane = (b, width, 256) if score == "adc" else (b, 1, width)
    text = jax.jit(lambda *z: gather_score(*z, score=score, emit="admit", rb=8,
                                           interpret=True)).lower(
        jax.ShapeDtypeStruct((b, v), jnp.int32), jax.ShapeDtypeStruct((n + 1, width), dtype),
        jax.ShapeDtypeStruct((n + 1, a), jnp.float32), jax.ShapeDtypeStruct(lane, jnp.float32),
        jax.ShapeDtypeStruct((b, t, a), jnp.float32), jax.ShapeDtypeStruct((b, t, a), jnp.float32),
    ).as_text(debug_info=True)
    assert ("compass/row_gather/xla" in text) == scoped


def test_visit_step_vmapped_matches_ref():
    """The engine vmaps per-query visit_step over the batch — blocking and
    the scalar-prefetch grid must survive batching bitwise."""
    rng = np.random.default_rng(32)
    b, n, d, a, t, v = 4, 100, 16, 2, 2, 24
    vectors, attrs = _mk_corpus(rng, n, d, a)
    live = jnp.asarray(rng.uniform(size=n + 1) > 0.2)
    idx = jnp.asarray(rng.integers(0, n + 1, (b, v)).astype(np.int32))
    mask = jnp.asarray(rng.uniform(size=(b, v)) > 0.3)
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    lo = jnp.asarray(rng.uniform(0, 0.5, (t, a)).astype(np.float32))
    hi = jnp.asarray(rng.uniform(0.5, 1.0, (t, a)).astype(np.float32))

    def run(fn):
        return jax.jit(
            lambda qs, ids, ms: jax.vmap(
                lambda q1, i1, m1: fn(vectors, attrs, live, i1, m1, q1, lo, hi)
            )(qs, ids, ms)
        )(q, idx, mask)

    (d_k, ad_k) = run(lambda *z: ops.visit_step(*z, metric="l2"))
    (d_r, ad_r) = run(lambda *z: ref.visit_step_ref(*z, "l2"))
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))
    np.testing.assert_array_equal(np.asarray(ad_k), np.asarray(ad_r))


# ---------------------------------------------------------------------------
# Per-shape block autotuner (kernels/autotune.py) + env pin resolution
# (kernels/interpret.py REPRO_PALLAS_BLOCK_*).
# ---------------------------------------------------------------------------


def test_autotune_pin_beats_measured_table(monkeypatch):
    from repro.kernels import autotune

    autotune.clear()
    cands = [{"rb": 4}, {"rb": 1}, {"rb": 8}]
    # pre-populate the measured table with a different winner
    autotune._TABLE[("visit_step", ("x",))] = {"rb": 8}
    monkeypatch.setenv("REPRO_PALLAS_BLOCK_VISIT_STEP", "rb=2")
    got = autotune.choose("visit_step", ("x",), cands)
    assert got == {"rb": 2}  # env pin wins over the measured table
    monkeypatch.delenv("REPRO_PALLAS_BLOCK_VISIT_STEP")
    assert autotune.choose("visit_step", ("x",), cands) == {"rb": 8}
    autotune.clear()


def test_autotune_pin_fills_missing_fields(monkeypatch):
    from repro.kernels import autotune

    autotune.clear()
    cands = [{"bb": 8, "bc": 128, "bd": 128}, {"bb": 16, "bc": 128, "bd": 128}]
    monkeypatch.setenv("REPRO_PALLAS_BLOCK_IVF_SCORE", "bb=4")
    got = autotune.choose("ivf_score", ("y",), cands)
    assert got == {"bb": 4, "bc": 128, "bd": 128}  # defaults fill the rest
    autotune.clear()


def test_autotune_measures_each_shape_once(monkeypatch):
    from repro.kernels import autotune

    autotune.clear()
    monkeypatch.setenv("REPRO_PALLAS_AUTOTUNE", "1")
    calls = []

    def fake_measure(cand):
        # _measure wall-clocks the call, so the cost difference must be
        # real time, not a return value — equal-cost fakes made the
        # winner timing noise (flaky under a loaded suite)
        calls.append(dict(cand))
        time.sleep(0.02 if cand["rb"] == 4 else 0.001)

    cands = [{"rb": 4}, {"rb": 2}]
    got1 = autotune.choose("visit_step", ("shape_a",), cands, fake_measure)
    n_after_first = len(calls)
    got2 = autotune.choose("visit_step", ("shape_a",), cands, fake_measure)
    assert got1 == got2 == {"rb": 2}  # fastest candidate cached
    # every candidate was probed (warmup + reps each), but the second choose
    # hit the table: measured once per shape, not per call
    assert {c["rb"] for c in calls} == {4, 2} and len(calls) == n_after_first
    assert autotune._N_MEASURED[("visit_step", ("shape_a",))] == 1
    autotune.choose("visit_step", ("shape_b",), cands, fake_measure)
    assert len(calls) > n_after_first  # a new shape re-measures
    autotune.clear()


def test_autotune_disabled_uses_default(monkeypatch):
    from repro.kernels import autotune

    autotune.clear()
    monkeypatch.setenv("REPRO_PALLAS_AUTOTUNE", "0")
    calls = []

    def fake_measure(cand):
        calls.append(cand)
        return 1.0

    got = autotune.choose("visit_step", ("z",), [{"rb": 4}, {"rb": 2}], fake_measure)
    assert got == {"rb": 4} and not calls  # candidates[0], nothing measured
    autotune.clear()


def test_autotune_raises_when_every_candidate_is_refused(monkeypatch):
    from repro.kernels import autotune

    autotune.clear()
    monkeypatch.setenv("REPRO_PALLAS_AUTOTUNE", "1")

    def refuse(cand):
        raise ValueError(f"tiling {cand} refused")

    with pytest.raises(RuntimeError, match="every block candidate was refused"):
        autotune.choose("visit_step", ("refused",), [{"rb": 4}, {"rb": 8}], refuse)
    assert ("visit_step", ("refused",)) not in autotune._TABLE
    autotune.clear()


def test_autotune_never_times_a_tracer(monkeypatch):
    """Traced (inside jit) the wrapper takes the default without timing;
    called with concrete arrays it measures on those very arrays."""
    from repro.kernels import autotune

    autotune.clear()
    monkeypatch.setenv("REPRO_PALLAS_AUTOTUNE", "1")
    rng = np.random.default_rng(34)
    n, d, a, t, v = 60, 8, 2, 1, 16
    vectors, attrs = _mk_corpus(rng, n, d, a)
    idx = jnp.asarray(rng.integers(0, n, v).astype(np.int32))
    mask = jnp.ones((v,), bool)
    q = jnp.asarray(rng.normal(size=d).astype(np.float32))
    lo, hi = jnp.zeros((t, a), jnp.float32), jnp.ones((t, a), jnp.float32)
    jax.jit(lambda *z: ops.visit_step(*z))(vectors, attrs, None, idx, mask, q, lo, hi)
    assert not autotune._N_MEASURED and not autotune._TABLE
    assert {d["source"] for d in autotune.decisions().values()} == {"default"}
    got = ops.visit_step(vectors, attrs, None, idx, mask, q, lo, hi)
    assert list(autotune._N_MEASURED.values()) == [1]
    assert {d["source"] for d in autotune.decisions().values()} == {"measured"}
    want = ref.visit_step_ref(vectors, attrs, None, idx, mask, q, lo, hi)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=1e-6)
    autotune.clear()


def test_visit_step_env_pin_end_to_end(monkeypatch):
    """A pinned rb must actually reach the kernel — and, because blocking
    never changes the math, stay bitwise identical to the ref oracle."""
    from repro.kernels import autotune

    autotune.clear()
    monkeypatch.setenv("REPRO_PALLAS_BLOCK_VISIT_STEP", "rb=2")
    rng = np.random.default_rng(33)
    n, d, a, t, v = 80, 12, 2, 2, 21
    vectors, attrs = _mk_corpus(rng, n, d, a)
    idx = jnp.asarray(rng.integers(0, n + 1, v).astype(np.int32))
    mask = jnp.asarray(rng.uniform(size=v) > 0.3)
    q = jnp.asarray(rng.normal(size=d).astype(np.float32))
    lo = jnp.asarray(rng.uniform(0, 0.5, (t, a)).astype(np.float32))
    hi = jnp.asarray(rng.uniform(0.5, 1.0, (t, a)).astype(np.float32))
    (d_k, ad_k), (d_r, ad_r) = _both_jitted(
        lambda *z: ops.visit_step(*z, metric="l2"),
        lambda *z: ref.visit_step_ref(*z, "l2"),
        vectors, attrs, None, idx, mask, q, lo, hi,
    )
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))
    np.testing.assert_array_equal(np.asarray(ad_k), np.asarray(ad_r))
    autotune.clear()


def test_block_override_parsing(monkeypatch):
    from repro.kernels.interpret import block_override

    monkeypatch.delenv("REPRO_PALLAS_BLOCK_VISIT_STEP", raising=False)
    assert block_override("visit_step") == {}
    monkeypatch.setenv("REPRO_PALLAS_BLOCK_VISIT_STEP", "rb=4")
    assert block_override("visit_step") == {"rb": 4}
    monkeypatch.setenv("REPRO_PALLAS_BLOCK_IVF_SCORE", "bb=8, bc=256")
    assert block_override("ivf_score") == {"bb": 8, "bc": 256}
    monkeypatch.setenv("REPRO_PALLAS_BLOCK_VISIT_STEP", "rb=four")
    with pytest.raises(ValueError, match="REPRO_PALLAS_BLOCK_VISIT_STEP"):
        block_override("visit_step")
