"""Multi-tenant serving tests: the CollectionService front door.

Contracts under test (ISSUE 10): exact-tier result-cache hits are
bitwise-identical to an uncached search and invalidated by the owning
collection's epoch swap only; the near-duplicate tier keys on the
collection's *own* PQ codes and never serves across collections;
interleaved writes to different collections never surface each other's
gids; executables are shared across tenants whose shape keys collapse;
overload sheds with a typed ``Rejected``; weighted-fair scheduling gives
a hot tenant its configured share; and the widened ``(bucket, shard,
tenant)`` obs schema stays back-compatible with old exports.
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

from repro.core import predicate as P
from repro.core.index import BuildConfig, build_index
from repro.core.mutable import MutableIndex, mutable_search
from repro.core.quant import QuantConfig
from repro.core.quant.encode import encode_rows, quantize_index
from repro.compass import (
    CollectionClient,
    CollectionService,
    CompassParams,
    Rejected,
    ShapePolicy,
)
from repro.obs import events as obs_ev
from repro.obs import health as obs_h
from repro.obs import registry as obs_reg
from repro.obs import slo as obs_slo
from repro.obs import timeseries as obs_ts
from repro.serving.rag import RagIndex

D = 8
N_ATTRS = 4
SHAPE = ShapePolicy(min_rows=512, delta_cap=32)
PM = CompassParams(k=8, ef=16, shape=SHAPE)
CFG = BuildConfig(m=8, nlist=8, kmeans_iters=2)


@pytest.fixture(autouse=True)
def _isolated_obs():
    """Same isolation contract as test_obs: clean registry, obs off, no
    leakage of enablement into the rest of the suite."""
    prev = obs_reg.set_enabled(False)
    obs_reg.reset()
    obs_ev.EVENTS.clear()
    yield
    obs_reg.set_enabled(prev)
    obs_reg.reset()
    obs_ev.EVENTS.clear()


def _mut(n: int, seed: int, gid_base: int = 0) -> MutableIndex:
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, D)).astype(np.float32)
    at = rng.uniform(size=(n, N_ATTRS)).astype(np.float32)
    return MutableIndex.build(
        x, at, CFG, delta_cap=32, shape=SHAPE,
        gids=np.arange(gid_base, gid_base + n, dtype=np.int64),
    )


def _svc(**kw) -> CollectionService:
    kw.setdefault("batch_size", 4)
    kw.setdefault("max_wait_s", 0.0)
    return CollectionService(PM, **kw)


def _qp(seed: int = 0):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=D).astype(np.float32)
    pred = P.Pred.range(0, 0.1, 0.9)
    return q, pred


def _result_of(rid, results):
    (r,) = [rr for rr in results if rr.rid == rid]
    return r


# -- exact-tier cache: bitwise parity + scoped invalidation -------------------


def test_exact_cache_hit_bitwise_identical_to_uncached():
    svc = _svc()
    client = svc.create("a", _mut(300, 0), cache_capacity=16)
    q, pred = _qp()

    r1 = _result_of(client.submit(q, pred), svc.flush())
    assert r1.cache_tier is None  # cold cache: a live search
    r2 = _result_of(client.submit(q, pred), svc.flush())
    assert r2.cache_tier == "exact"

    np.testing.assert_array_equal(r2.ids, r1.ids)
    np.testing.assert_array_equal(
        r2.dists.view(np.uint32), r1.dists.view(np.uint32)
    )
    # and both match a direct uncached search on the same snapshot
    direct = client.mutable.search(
        q[None], P.stack_predicates([pred.tensor(N_ATTRS)]), PM
    )
    np.testing.assert_array_equal(r2.ids, np.asarray(direct.ids)[0, : PM.k])
    np.testing.assert_array_equal(
        r2.dists.view(np.uint32),
        np.asarray(direct.dists)[0, : PM.k].view(np.uint32),
    )
    st = client.stats()["cache"]
    assert st["hits_exact"] == 1 and st["misses"] == 1


def test_epoch_swap_invalidates_only_the_owning_collection():
    svc = _svc()
    a = svc.create("a", _mut(300, 0), cache_capacity=16)
    b = svc.create("b", _mut(360, 1), cache_capacity=16)
    qa, pa = _qp(0)
    qb, pb = _qp(1)
    for client, q, p in ((a, qa, pa), (b, qb, pb)):
        client.submit(q, p)
    svc.flush()
    # both caches warm
    assert _result_of(a.submit(qa, pa), svc.flush()).cache_tier == "exact"
    assert _result_of(b.submit(qb, pb), svc.flush()).cache_tier == "exact"

    a.compact()  # epoch swap on A, done via the operator surface
    ra = _result_of(a.submit(qa, pa), svc.flush())
    rb = _result_of(b.submit(qb, pb), svc.flush())
    assert ra.cache_tier is None  # A's entries dropped
    assert rb.cache_tier == "exact"  # B untouched


def test_write_application_invalidates_the_writer_only():
    svc = _svc()
    a = svc.create("a", _mut(300, 0), cache_capacity=16)
    b = svc.create("b", _mut(360, 1), cache_capacity=16)
    qa, pa = _qp(0)
    qb, pb = _qp(1)
    a.submit(qa, pa)
    b.submit(qb, pb)
    svc.flush()
    rng = np.random.default_rng(7)
    a.submit_upsert(
        9000,
        rng.normal(size=D).astype(np.float32),
        rng.uniform(size=N_ATTRS).astype(np.float32),
    )
    svc.step()  # applies A's upsert -> A's cache dropped
    assert _result_of(a.submit(qa, pa), svc.flush()).cache_tier is None
    assert _result_of(b.submit(qb, pb), svc.flush()).cache_tier == "exact"


# -- near-duplicate tier: own-codebook keys, never cross-collection -----------


def _quantized_immutable(n: int, seed: int):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 16)).astype(np.float32)
    at = rng.uniform(size=(n, N_ATTRS)).astype(np.float32)
    idx = build_index(x, at, BuildConfig(m=8, nlist=8, kmeans_iters=2))
    return quantize_index(idx, QuantConfig(m=4, ks=16, iters=2))


def test_near_tier_hits_on_same_code_and_never_crosses_collections():
    pm = CompassParams(k=8, ef=16)
    svc = CollectionService(pm, batch_size=4, max_wait_s=0.0)
    ia = _quantized_immutable(400, 0)
    ib = _quantized_immutable(400, 1)
    a = svc.create("a", ia, cache_capacity=16, near_cache=True)
    b = svc.create("b", ib, cache_capacity=16, near_cache=True)

    rng = np.random.default_rng(2)
    q = rng.normal(size=16).astype(np.float32)
    q2 = q + np.float32(1e-6)  # different bytes, same PQ cell
    qv = ia.qvecs
    c1 = np.asarray(encode_rows(qv.codebooks, qv.mean, q[None]))
    c2 = np.asarray(encode_rows(qv.codebooks, qv.mean, q2[None]))
    np.testing.assert_array_equal(c1, c2)  # test precondition
    assert q.tobytes() != q2.tobytes()

    pred = P.Pred.range(0, 0.0, 1.0)
    r1 = _result_of(a.submit(q, pred), svc.flush())
    assert r1.cache_tier is None
    r2 = _result_of(a.submit(q2, pred), svc.flush())
    assert r2.cache_tier == "near"  # exact key missed, code key hit
    np.testing.assert_array_equal(r2.ids, r1.ids)

    # the same near-duplicate submitted to B must NOT see A's entry: the
    # code word is keyed on the collection's own codebooks and the cache
    # itself is per-collection
    rb = _result_of(b.submit(q2, pred), svc.flush())
    assert rb.cache_tier is None
    assert b.stats()["cache"]["hits_near"] == 0
    assert a.stats()["cache"]["hits_near"] == 1


def test_near_cache_requires_quantized_index():
    svc = _svc()
    with pytest.raises(ValueError, match="near_cache"):
        svc.create("a", _mut(300, 0), cache_capacity=16, near_cache=True)


# -- cross-tenant isolation ---------------------------------------------------


def test_interleaved_writes_never_surface_across_collections():
    obs_reg.set_enabled(True)
    svc = _svc()
    a = svc.create("a", _mut(300, 0, gid_base=0), cache_capacity=0)
    b = svc.create("b", _mut(300, 1, gid_base=100_000), cache_capacity=0)
    rng = np.random.default_rng(3)
    for i in range(8):  # interleaved writes, distinct gid spaces
        va = rng.normal(size=D).astype(np.float32)
        vb = rng.normal(size=D).astype(np.float32)
        at = rng.uniform(size=N_ATTRS).astype(np.float32)
        a.submit_upsert(10_000 + i, va, at)
        b.submit_upsert(110_000 + i, vb, at)
    svc.step()

    q, pred = _qp(4)
    ra = _result_of(a.submit(q, pred), svc.flush())
    rb = _result_of(b.submit(q, pred), svc.flush())
    ids_a = set(ra.ids[ra.ids >= 0].tolist())
    ids_b = set(rb.ids[rb.ids >= 0].tolist())
    assert ids_a and ids_b
    assert all(g < 100_000 for g in ids_a)  # only A's gid space
    assert all(g >= 100_000 for g in ids_b)  # only B's gid space
    assert not (ids_a & ids_b)

    # per-tenant accounting is disjoint under the tenant label
    reg = obs_reg.registry()
    assert reg.get("compass_submitted_total").value(tenant="a") == 1.0
    assert reg.get("compass_submitted_total").value(tenant="b") == 1.0
    served = reg.get("compass_serve_requests_total")
    tenants = {s["labels"]["tenant"] for s in served.samples()}
    assert {"a", "b"} <= tenants
    sa = svc.collection_stats("a")
    sb = svc.collection_stats("b")
    assert sa["n_upserts"] == 8 and sb["n_upserts"] == 8


# -- load shedding ------------------------------------------------------------


def test_overload_sheds_typed_rejected_and_counts_it():
    obs_reg.set_enabled(True)
    svc = _svc()
    client = svc.create("tiny", _mut(300, 0), max_queue_depth=2, cache_capacity=0)
    rng = np.random.default_rng(5)
    outcomes = []
    for i in range(6):
        q = rng.normal(size=D).astype(np.float32)
        outcomes.append(client.submit(q, _qp()[1]))
    shed = [o for o in outcomes if isinstance(o, Rejected)]
    rids = [o for o in outcomes if not isinstance(o, Rejected)]
    assert len(rids) == 2 and len(shed) == 4
    for rej in shed:
        assert rej.collection == "tiny"
        assert rej.reason == "queue_depth"
        assert rej.limit == 2 and rej.queue_depth == 2
    # accepted work still completes; nothing was silently dropped
    results = svc.flush()
    assert {r.rid for r in results} == set(rids)
    assert client.stats()["n_shed"] == 4
    reg = obs_reg.registry()
    assert reg.get("compass_shed_total").value(tenant="tiny") == 4.0
    assert reg.get("compass_submitted_total").value(tenant="tiny") == 6.0


# -- executable sharing -------------------------------------------------------


def test_executables_shared_across_same_shape_tenants():
    svc = _svc()
    clients = {
        name: svc.create(name, _mut(n, i), cache_capacity=0)
        for i, (name, n) in enumerate((("a", 300), ("b", 360), ("c", 420)))
    }
    jit0 = mutable_search._cache_size()
    q, pred = _qp(6)
    for client in clients.values():
        client.submit(q, pred)
    svc.flush()
    # three tenants, one occupied (B, T, A, rows-bucket) shape -> at most
    # one compile, shared: all three corpora fold into the 512-row bucket
    # (0 when an earlier test in this process already traced the shape —
    # the global jit cache is exactly the sharing mechanism under test)
    assert mutable_search._cache_size() - jit0 <= 1
    assert svc.compile_count == 1
    for name in clients:
        st = svc.collection_stats(name)
        assert st["compiles"] == 1
        assert st["occupied_buckets"] == 1


# -- weighted-fair scheduling -------------------------------------------------


def test_wfq_gives_the_hot_tenant_its_weighted_share():
    svc = _svc(max_batches_per_step=1)
    hot = svc.create("hot", _mut(300, 0), weight=4.0, cache_capacity=0)
    cold = svc.create("cold", _mut(360, 1), weight=1.0, cache_capacity=0)
    rng = np.random.default_rng(8)
    pred = _qp()[1]
    for _ in range(10 * svc.batch_size):  # 10 full batches per tenant
        hot.submit(rng.normal(size=D).astype(np.float32), pred)
        cold.submit(rng.normal(size=D).astype(np.float32), pred)
    order = []
    for _ in range(10):  # one micro-batch per step
        res = svc.step()
        assert len({r.collection for r in res}) == 1
        order.append(res[0].collection)
    # weight 4:1 -> the hot tenant owns ~8 of the first 10 batches, and
    # the cold tenant is never starved out entirely
    assert order.count("hot") >= 7
    assert order.count("cold") >= 1
    svc.flush()  # drain the rest; everything completes
    assert svc.pending() == 0


# -- rag routing --------------------------------------------------------------


def test_rag_make_service_routes_through_a_named_collection(built_index, corpus):
    _, _, queries = corpus
    rag = RagIndex(index=built_index, doc_tokens=np.zeros((4, 4), np.int32))
    client = rag.make_service(k=4, ef=16, cache_capacity=8)
    assert isinstance(client, CollectionClient)
    pred = P.Pred.range(0, 0.0, 1.0)
    rid = client.submit(queries[0], pred)
    r = _result_of(rid, client.run_until_idle())
    assert r.collection == "docs"
    assert r.ids.shape == (4,)
    assert client.stats()["compiles"] == 1

    # co-hosting: a shared service takes a second corpus as a second
    # collection, but refuses constructor kwargs it can no longer apply
    svc = client.service
    rag2 = RagIndex(index=built_index, doc_tokens=np.zeros((4, 4), np.int32))
    c2 = rag2.make_service(collection="docs2", service=svc, cache_capacity=8)
    assert set(svc.collections()) == {"docs", "docs2"}
    with pytest.raises(ValueError, match="fresh service"):
        rag2.make_service(collection="docs3", service=svc, batch_size=2)
    assert c2.submit(queries[1], pred) is not None


# -- obs: widened label schema stays back-compatible --------------------------


def test_old_narrow_label_exports_still_validate(tmp_path):
    # a registry written before the tenant dimension existed: the same
    # family names with the old (bucket, shard) label set must still
    # round-trip through the schema gate
    old = obs_reg.MetricsRegistry()
    c = old.counter("compass_queries_total", "q", ("bucket", "shard"))
    c.inc(3, bucket="(8, 1)", shard="")
    h = old.histogram(
        "compass_serve_exec_seconds", "t", ("bucket",), buckets=(0.01, 0.1, 1.0)
    )
    h.observe(0.05, bucket="(8, 1)")
    payload = old.to_json()
    assert obs_reg.validate_export(payload) == []
    path = tmp_path / "METRICS.json"
    path.write_text(json.dumps(payload))
    from repro.obs.validate import validate_any_file

    assert validate_any_file(str(path)) == []


def test_widened_schema_records_and_validates():
    obs_reg.set_enabled(True)
    svc = _svc()
    client = svc.create("a", _mut(300, 0), cache_capacity=0)
    client.submit(*_qp())
    svc.flush()
    reg = obs_reg.registry()
    q = reg.get("compass_queries_total")
    assert q is not None
    for s in q.samples():
        assert set(s["labels"]) == {"bucket", "shard", "tenant"}
    assert obs_reg.validate_export(reg.to_json()) == []


# -- admission watchdog + per-tenant SLOs -------------------------------------


def test_admission_pressure_watchdog_grades_shed_rate_and_queue_fill():
    r = obs_reg.MetricsRegistry()
    ring = obs_ts.TimeSeriesRing(capacity=8)
    chk = obs_h.admission_pressure(r, ring, now=1.0)
    assert chk.status == "ok" and "no collection service" in chk.detail

    sub = r.counter("compass_submitted_total", "s", ("tenant",))
    shed = r.counter("compass_shed_total", "s", ("tenant",))
    ring.snapshot(r, ts=0.0)
    sub.inc(100, tenant="hot")
    shed.inc(10, tenant="hot")  # 10% shed rate: past the 5% crit line
    sub.inc(100, tenant="cold")
    ring.snapshot(r, ts=1.0)
    chk = obs_h.admission_pressure(r, ring, now=1.0)
    assert chk.status == "crit"
    assert "'hot'" in chk.detail and chk.value == pytest.approx(0.10)
    assert chk.remediation

    # queue fill is a leading indicator: escalates an otherwise-ok verdict
    r2 = obs_reg.MetricsRegistry()
    ring2 = obs_ts.TimeSeriesRing(capacity=8)
    r2.counter("compass_submitted_total", "s", ("tenant",)).inc(100, tenant="a")
    r2.gauge("compass_queue_depth", "d", ("tenant",)).set(90, tenant="a")
    r2.gauge("compass_queue_limit", "l", ("tenant",)).set(100, tenant="a")
    ring2.snapshot(r2, ts=0.0)
    ring2.snapshot(r2, ts=1.0)
    chk2 = obs_h.admission_pressure(r2, ring2, now=1.0)
    assert chk2.status == "warn"  # 90% fill: warn, not yet crit
    assert "90%" in chk2.detail


def test_tenant_slos_scope_to_the_tenant_label():
    specs = obs_slo.tenant_slos("hot", latency_threshold_s=0.1)
    by_name = {s.name: s for s in specs}
    assert set(by_name) == {"serve_latency:hot", "admission:hot"}
    lat = by_name["serve_latency:hot"]
    assert lat.kind == "latency" and lat.threshold == 0.1
    assert lat.labels == {"tenant": "hot"}
    adm = by_name["admission:hot"]
    assert adm.kind == "ratio"
    assert adm.metric == "compass_shed_total"
    assert adm.total_metric == "compass_submitted_total"
    assert adm.labels == {"tenant": "hot"}


# -- service-level invariants -------------------------------------------------


def test_duplicate_and_mismatched_collections_fail_at_create():
    svc = _svc()
    svc.create("a", _mut(300, 0))
    with pytest.raises(ValueError, match="already exists"):
        svc.create("a", _mut(300, 1))
    other_shape = dataclasses.replace(SHAPE, min_rows=256)
    rng = np.random.default_rng(9)
    x = rng.normal(size=(300, D)).astype(np.float32)
    at = rng.uniform(size=(300, N_ATTRS)).astype(np.float32)
    mismatched = MutableIndex.build(x, at, CFG, delta_cap=32, shape=other_shape)
    with pytest.raises(ValueError, match="ShapePolicy"):
        svc.create("b", mismatched)
    with pytest.raises(KeyError, match="unknown collection"):
        svc.collection("nope")


def test_drop_discards_queued_work_but_keeps_shared_executables():
    svc = _svc()
    a = svc.create("a", _mut(300, 0), cache_capacity=0)
    b = svc.create("b", _mut(360, 1), cache_capacity=0)
    q, pred = _qp()
    a.submit(q, pred)
    svc.flush()
    n = svc.compile_count
    b.submit(q, pred)
    svc.drop("b")
    assert svc.collections() == ("a",)
    assert svc.pending() == 0
    assert svc.compile_count == n  # shared shapes outlive the tenant
    # the surviving tenant still serves without a recompile
    a.submit(q, pred)
    svc.flush()
    assert svc.compile_count == n


def test_program_texts_name_the_served_programs_and_their_stages(tmp_path):
    """Each bucket label's compiled program, on the mutable and the AOT path,
    carries the engine's stage scopes; the service's host phases are spans
    of a profiler trace around the micro-batch."""
    import jax

    from repro.obs import profiling as obs_prof

    svc = _svc()
    svc.create("m", _mut(300, 1))
    rng = np.random.default_rng(2)
    svc.create("i", build_index(rng.normal(size=(300, D)).astype(np.float32),
                                rng.uniform(size=(300, N_ATTRS)).astype(np.float32), CFG))
    jax.profiler.start_trace(str(tmp_path))
    for name in ("m", "i"):
        q, pred = _qp(3)
        svc.submit(name, q, pred)
        svc.run_until_idle()
    jax.profiler.stop_trace()
    texts = svc.program_texts()
    assert set(texts) == {"B4xT1"}  # one label: the later, AOT, program wins
    assert "compass/engine/loop" in texts["B4xT1"]
    assert "compass/visit_step" in texts["B4xT1"] or "compass/engine/visit" in texts["B4xT1"]
    assert obs_prof.SERVED.texts()["B4xT1"] == texts["B4xT1"]

    from bench import tracing

    spans = {sp[0] for sp in tracing.read(tracing.find(str(tmp_path)), window_ns=(0, 1 << 62))
             .spans}
    assert {"compass/serve/writes", "compass/serve/pack", "compass/serve_batch/B4xT1",
            "compass/serve/unpack", "compass/serve/gauges"} <= spans
