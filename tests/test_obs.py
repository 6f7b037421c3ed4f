"""Observability tests: registry units + exports, explain traces (per-mode
est-vs-actual selectivity, determinism, bitwise invariance on ref AND
pallas), event log + JSONL sink, mutable/serving/distributed wiring, and
the kernel fallback/autotune counters.

The two contracts under test everywhere: obs OFF means results are bitwise
identical to a build without the subsystem, and obs ON changes nothing
about the traced program (recording happens host-side at existing sync
points only).
"""
from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import predicate as P
from repro.core.engine import CompassParams, compass_search
from repro.core.planner import plan as QP
from repro.obs import events as obs_ev
from repro.obs import registry as obs_reg
from repro.obs.trace import QueryTrace, explain, kernel_route


@pytest.fixture(autouse=True)
def _isolated_obs():
    """Every test starts with a clean registry/event log and obs disabled,
    and cannot leak its enablement into the rest of the suite."""
    prev = obs_reg.set_enabled(False)
    obs_reg.reset()
    obs_ev.EVENTS.clear()
    yield
    obs_reg.set_enabled(prev)
    obs_reg.reset()
    obs_ev.EVENTS.clear()
    obs_ev.EVENTS.configure(None)


def _preds(rng, n_queries, n_attrs, passrate, n_terms):
    preds = []
    for _ in range(n_queries):
        terms = []
        for a in range(n_terms):
            lo = rng.uniform(0, 1 - passrate)
            terms.append(P.Pred.range(a, lo, lo + passrate))
        preds.append(P.Pred.and_(*terms).tensor(n_attrs))
    return P.stack_predicates(preds)


# -- registry units -----------------------------------------------------------


def test_counter_gauge_histogram_basics():
    r = obs_reg.MetricsRegistry()
    c = r.counter("compass_test_total", "help", ("shard",))
    c.inc(shard="0")
    c.inc(2.5, shard="0")
    c.inc(shard="1")
    assert c.value(shard="0") == pytest.approx(3.5)
    assert c.value(shard="1") == pytest.approx(1.0)
    with pytest.raises(ValueError):
        c.inc(-1, shard="0")
    with pytest.raises(ValueError):  # labels must match labelnames exactly
        c.inc(bucket="B8")
    g = r.gauge("compass_test_epoch")
    g.set(7)
    assert g.value() == 7.0
    h = r.histogram("compass_test_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    counts, total, n = h.series()
    assert list(counts) == [1, 1, 1] and n == 3 and total == pytest.approx(5.55)


def test_registry_redeclare_conflicts():
    r = obs_reg.MetricsRegistry()
    r.counter("compass_x_total", labelnames=("kind",))
    with pytest.raises(ValueError):
        r.gauge("compass_x_total")  # type conflict
    with pytest.raises(ValueError):
        r.counter("compass_x_total", labelnames=("other",))  # labelname conflict
    with pytest.raises(ValueError):
        r.counter("0bad-name")  # illegal prometheus name


def test_export_json_and_prometheus_validate():
    r = obs_reg.MetricsRegistry()
    r.counter("compass_q_total", "queries", ("mode",)).inc(3, mode="prefilter")
    r.gauge("compass_epoch", "epoch").set(2)
    h = r.histogram("compass_lat_seconds", "latency", buckets=(0.01, 0.1))
    h.observe(0.05)
    payload = r.to_json()
    assert payload["schema"] == obs_reg.SCHEMA
    assert obs_reg.validate_export(payload) == []
    text = r.to_prometheus()
    assert '# TYPE compass_q_total counter' in text
    assert 'compass_q_total{mode="prefilter"} 3' in text
    # cumulative le buckets + the +Inf terminator
    assert 'le="0.1"' in text and 'le="+Inf"' in text
    assert "compass_lat_seconds_count" in text


def test_prometheus_hist_inf_sum_count_consistency():
    """The text exposition's histogram lines must be internally consistent:
    cumulative ``le`` counts non-decreasing, the +Inf bucket equal to
    ``_count``, and ``_sum`` present — the invariants a Prometheus scraper
    relies on."""
    r = obs_reg.MetricsRegistry()
    h = r.histogram("compass_lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 7.0):
        h.observe(v)
    lines = [ln for ln in r.to_prometheus().splitlines() if not ln.startswith("#")]
    bucket_lines = [ln for ln in lines if ln.startswith("compass_lat_seconds_bucket")]
    cum = [float(ln.rsplit(" ", 1)[1]) for ln in bucket_lines]
    assert cum == sorted(cum)  # cumulative counts never decrease
    assert 'le="+Inf"' in bucket_lines[-1]
    count = next(
        float(ln.rsplit(" ", 1)[1])
        for ln in lines
        if ln.startswith("compass_lat_seconds_count")
    )
    total = next(
        float(ln.rsplit(" ", 1)[1])
        for ln in lines
        if ln.startswith("compass_lat_seconds_sum")
    )
    assert cum[-1] == count == 4
    assert total == pytest.approx(8.05)


def test_label_escaping_roundtrip():
    r"""Label values carrying backslashes, quotes and newlines must escape
    in the text exposition and survive a JSON export -> from_json
    reconstruction byte-for-byte."""
    nasty = 'a"b\\c\nd'
    r = obs_reg.MetricsRegistry()
    r.counter("compass_q_total", "q", ("tag",)).inc(2, tag=nasty)
    text = r.to_prometheus()
    assert 'tag="a\\"b\\\\c\\nd"' in text
    assert "\n" not in text.split("compass_q_total{", 1)[1].split("}", 1)[0]
    payload = r.to_json()
    assert obs_reg.validate_export(payload) == []
    r2 = obs_reg.MetricsRegistry.from_json(json.loads(json.dumps(payload)))
    assert r2.get("compass_q_total").value(tag=nasty) == 2.0
    assert r2.to_prometheus() == text


def test_truncated_metrics_json_rejected(tmp_path):
    """A METRICS.json cut off mid-write (partial disk flush, killed run)
    must fail validation loudly, not parse as a smaller registry."""
    from repro.obs.validate import validate_any_file

    r = obs_reg.MetricsRegistry()
    r.counter("compass_q_total", "q").inc(3)
    r.histogram("compass_lat_seconds", "l", buckets=(0.1,)).observe(0.05)
    blob = json.dumps(r.to_json(), indent=1)
    good = tmp_path / "METRICS.json"
    good.write_text(blob)
    assert validate_any_file(str(good)) == []
    truncated = tmp_path / "TRUNC.json"
    truncated.write_text(blob[: len(blob) // 2])
    errs = validate_any_file(str(truncated))
    assert errs and "malformed JSON" in errs[0]
    # histogram invariants: count must equal the bucket-count sum
    bad = json.loads(blob)
    for m in bad["metrics"]:
        if m["type"] == "histogram":
            m["samples"][0]["count"] += 1
    (tmp_path / "BADSUM.json").write_text(json.dumps(bad))
    assert validate_any_file(str(tmp_path / "BADSUM.json"))


def test_validate_export_catches_corruption():
    r = obs_reg.MetricsRegistry()
    r.counter("compass_ok_total").inc()
    good = r.to_json()
    bad = json.loads(json.dumps(good))
    bad["metrics"][0]["name"] = "not a legal name!"
    assert obs_reg.validate_export(bad)
    bad2 = json.loads(json.dumps(good))
    bad2["schema"] = "something/else"
    assert obs_reg.validate_export(bad2)


# -- explain traces -----------------------------------------------------------


def test_explain_flag_shapes_and_bitwise(built_index, corpus):
    _, _, queries = corpus
    rng = np.random.default_rng(3)
    qj = jnp.asarray(queries[:8])
    pred = _preds(rng, 8, 4, 0.45, 2)
    pm = CompassParams(k=10, ef=32, planner=True, backend="ref")
    res = compass_search(built_index, qj, pred, pm)
    out = compass_search(built_index, qj, pred, pm, explain=True)
    assert isinstance(out, tuple) and len(out) == 2
    res2, traces = out
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(res2.ids))
    np.testing.assert_array_equal(np.asarray(res.dists), np.asarray(res2.dists))
    assert len(traces) == 8 and all(isinstance(t, QueryTrace) for t in traces)
    # explain=False (the default) returns the bare SearchResult, not a
    # (result, traces) pair — SearchResult is itself a NamedTuple, so probe
    # the wrapper shape, not tuple-ness
    assert isinstance(res2, type(res)) and hasattr(res, "ids")
    rendered = explain(traces)
    assert "selectivity est=" in rendered and "mode=" in rendered


def test_explain_determinism(built_index, corpus):
    _, _, queries = corpus
    rng = np.random.default_rng(4)
    qj = jnp.asarray(queries[:4])
    pred = _preds(rng, 4, 4, 0.45, 2)
    pm = CompassParams(k=10, ef=32, planner=True, backend="ref")
    _, t1 = compass_search(built_index, qj, pred, pm, explain=True)
    _, t2 = compass_search(built_index, qj, pred, pm, explain=True)
    assert t1 == t2  # frozen dataclasses of host scalars: exact equality


@pytest.mark.parametrize(
    "passrate,n_terms,want_mode,want_name",
    [
        (0.01, 1, QP.PREFILTER, "prefilter"),
        (0.45, 2, QP.COOPERATIVE, "cooperative"),
        (0.99, 1, QP.POSTFILTER, "postfilter"),
    ],
)
def test_explain_selectivity_per_mode(
    built_index, corpus, passrate, n_terms, want_mode, want_name
):
    """Each planner mode yields traces with BOTH the planner's estimate and
    the measured actual selectivity populated and sane."""
    _, _, queries = corpus
    rng = np.random.default_rng(5)
    qj = jnp.asarray(queries[:8])
    pred = _preds(rng, 8, 4, passrate, n_terms)
    pm = CompassParams(k=10, ef=64, planner=True, backend="ref")
    res, traces = compass_search(built_index, qj, pred, pm, explain=True)
    assert np.all(np.asarray(res.stats.mode) == want_mode)
    for t in traces:
        assert t.mode == want_name
        assert t.planner is True
        assert t.est_selectivity is not None and 0.0 <= t.est_selectivity <= 1.0
        assert t.actual_selectivity is not None and 0.0 <= t.actual_selectivity <= 1.0
        assert t.run_total is not None and t.run_total >= 0
        assert t.kernel_route == "ref"
    # the estimate should be in the right regime for the extremes
    if want_mode == QP.PREFILTER:
        assert all(t.est_selectivity < 0.1 for t in traces)
    if want_mode == QP.POSTFILTER:
        assert all(t.est_selectivity > 0.5 for t in traces)


def test_planner_off_trace_fields_none(built_index, corpus):
    _, _, queries = corpus
    rng = np.random.default_rng(6)
    qj = jnp.asarray(queries[:4])
    pred = _preds(rng, 4, 4, 0.45, 2)
    pm = CompassParams(k=10, ef=32, planner=False, backend="ref")
    _, traces = compass_search(built_index, qj, pred, pm, explain=True)
    for t in traces:
        assert t.planner is False
        assert t.est_selectivity is None and t.run_total is None
        # measured selectivity still reports — it comes from SearchStats
        assert t.actual_selectivity is not None


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_obs_enabled_is_bitwise_invariant(built_index, corpus, backend):
    """Flipping the registry on (and recording into it) must not change a
    single bit of ids or dists, on the jnp path AND the kernel path."""
    _, _, queries = corpus
    rng = np.random.default_rng(7)
    qj = jnp.asarray(queries[:4])
    pred = _preds(rng, 4, 4, 0.45, 2)
    pm = CompassParams(k=10, ef=32, planner=True, backend=backend)
    off = compass_search(built_index, qj, pred, pm)
    obs_reg.set_enabled(True)
    on = compass_search(built_index, qj, pred, pm)
    obs_reg.record_search_stats(on.stats)  # recording is host-side only
    np.testing.assert_array_equal(np.asarray(off.ids), np.asarray(on.ids))
    np.testing.assert_array_equal(np.asarray(off.dists), np.asarray(on.dists))
    assert (
        obs_reg.registry()
        .get("compass_queries_total")
        .value(bucket="", shard="", tenant="")
        == 4
    )


def test_kernel_route_strings():
    pm = CompassParams(k=10, ef=32, backend="pallas")
    assert kernel_route(pm.resolved(), quant_active=False, metric="l2").startswith(
        "pallas/visit_step/"
    )
    assert kernel_route(pm.resolved(), quant_active=True, metric="ip").startswith(
        "pallas/pq_score/"
    )
    pm_unfused = CompassParams(k=10, ef=32, backend="pallas", fused_visit=False)
    assert kernel_route(
        pm_unfused.resolved(), quant_active=False, metric="l2"
    ).startswith("pallas/filter_distance/")
    assert kernel_route(pm.resolved(), quant_active=False, metric="weird") == (
        "ref(metric=weird)"
    )
    pm_ref = CompassParams(k=10, ef=32, backend="ref")
    assert kernel_route(pm_ref.resolved(), quant_active=False, metric="l2") == "ref"


def test_record_search_stats_noop_when_disabled(built_index, corpus):
    _, _, queries = corpus
    rng = np.random.default_rng(8)
    qj = jnp.asarray(queries[:2])
    pred = _preds(rng, 2, 4, 0.45, 1)
    res = compass_search(built_index, qj, pred, CompassParams(k=5, ef=32, backend="ref"))
    obs_reg.record_search_stats(res.stats)  # disabled: must not register
    assert obs_reg.registry().get("compass_queries_total") is None
    with pytest.raises(ValueError):
        obs_reg.set_enabled(True)
        obs_reg.record_search_stats(res.stats, labels={"nonsense": "x"})


# -- mutable tier: explain epoch, events, JSONL sink --------------------------


def _tiny_mutable(n=400, d=12, a=4, cap=32, seed=0):
    from repro.core.index import BuildConfig
    from repro.core.mutable import MutableIndex

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    at = rng.uniform(size=(n, a)).astype(np.float32)
    mi = MutableIndex.build(
        x, at, BuildConfig(m=8, nlist=8, kmeans_iters=3), delta_cap=cap
    )
    q = jnp.asarray(rng.normal(size=(4, d)).astype(np.float32))
    pred = P.stack_predicates([P.Pred.range(0, 0.0, 0.6).tensor(a)] * 4)
    return mi, q, pred, rng


def test_mutable_explain_carries_epoch():
    mi, q, pred, _ = _tiny_mutable()
    pm = CompassParams(k=5, ef=32, backend="ref")
    res, traces = mi.search(q, pred, pm, explain=True)
    assert all(t.epoch == mi.epoch for t in traces)
    res2 = mi.search(q, pred, pm)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(res2.ids))


def test_mutable_lifecycle_events_and_sink(tmp_path):
    sink = tmp_path / "events.jsonl"
    obs_ev.EVENTS.configure(str(sink))
    mi, q, pred, rng = _tiny_mutable(cap=16)
    d, a = 12, 4
    gid = mi.base.n_records
    for i in range(40):  # overflow the 16-slot delta -> forced compactions
        mi.upsert(
            gid + i,
            rng.normal(size=d).astype(np.float32),
            rng.uniform(size=a).astype(np.float32),
        )
    assert mi.epoch >= 1
    kinds = {e["kind"] for e in obs_ev.EVENTS.tail(200)}
    assert {"delta_overflow", "compaction", "epoch_swap"} <= kinds
    comp = obs_ev.EVENTS.tail(5, kind="compaction")[-1]
    assert comp["epoch"] == mi.epoch and comp["wall_s"] >= 0
    # the JSONL sink mirrors the ring, one parseable object per line
    lines = [json.loads(ln) for ln in sink.read_text().splitlines()]
    assert len(lines) == sum(obs_ev.EVENTS.counts().values())
    assert all("ts" in e and "kind" in e for e in lines)


def test_mutable_compaction_metrics_in_registry():
    obs_reg.set_enabled(True)
    mi, _, _, _ = _tiny_mutable()
    mi.compact()
    r = obs_reg.registry()
    assert r.get("compass_compactions_total").value() >= 1
    assert r.get("compass_epoch").value() == mi.epoch
    counts, _, n = r.get("compass_compaction_seconds").series()
    assert n >= 1 and sum(counts) == n
    assert obs_reg.validate_export(r.to_json()) == []


# -- distributed: aggregation semantics + shard labels ------------------------


def test_aggregate_shard_stats_semantics():
    from repro.core.distributed import (
        STATS_FIRST_FIELDS,
        STATS_MAX_FIELDS,
        STATS_SUM_FIELDS,
        aggregate_shard_stats,
    )
    from repro.core.engine import SearchStats

    # the classification must cover every SearchStats field exactly once
    all_classified = (
        set(STATS_SUM_FIELDS) | set(STATS_MAX_FIELDS) | set(STATS_FIRST_FIELDS)
    )
    assert all_classified == set(SearchStats._fields)
    assert (
        len(STATS_SUM_FIELDS) + len(STATS_MAX_FIELDS) + len(STATS_FIRST_FIELDS)
        == len(SearchStats._fields)
    )

    def mk(base):
        return SearchStats(
            n_dist=jnp.array([base, base + 1]),
            n_cdist=jnp.array([base] * 2),
            n_steps=jnp.array([base, 2 * base]),
            n_bcalls=jnp.array([1, 1]),
            n_clusters_ranked=jnp.array([2, 2]),
            n_adc=jnp.array([0, 0]),
            n_rerank=jnp.array([0, 0]),
            n_pass=jnp.array([base, base]),
            mode=jnp.array([base % 3, base % 3]),
            efs_final=jnp.array([32, 32]),
            est_sel=jnp.array([0.1 * base, 0.2]),
            run_total=jnp.array([5, 5]),
        )

    agg = aggregate_shard_stats([mk(10), mk(4)])
    np.testing.assert_array_equal(np.asarray(agg.n_dist), [14, 16])  # summed
    np.testing.assert_array_equal(np.asarray(agg.n_pass), [14, 14])  # summed
    np.testing.assert_array_equal(np.asarray(agg.n_steps), [10, 20])  # max
    np.testing.assert_array_equal(np.asarray(agg.mode), [1, 1])  # shard 0
    np.testing.assert_allclose(np.asarray(agg.est_sel), [1.0, 0.2])  # shard 0


def test_distributed_search_records_per_shard():
    from repro.core.distributed import DistributedMutableIndex
    from repro.core.index import BuildConfig

    rng = np.random.default_rng(11)
    n, d, a = 400, 12, 4
    x = rng.normal(size=(n, d)).astype(np.float32)
    at = rng.uniform(size=(n, a)).astype(np.float32)
    dmi = DistributedMutableIndex.build(
        x, at, 2, BuildConfig(m=8, nlist=8, kmeans_iters=3), delta_cap=32
    )
    assert dmi.shards[0].obs_labels == {"shard": "0"}
    assert dmi.shards[1].obs_labels == {"shard": "1"}
    q = jnp.asarray(rng.normal(size=(2, d)).astype(np.float32))
    pred = P.stack_predicates([P.Pred.range(0, 0.0, 0.6).tensor(a)] * 2)
    pm = CompassParams(k=5, ef=32, backend="ref")
    off = dmi.search(q, pred, pm)
    obs_reg.set_enabled(True)
    on = dmi.search(q, pred, pm)
    np.testing.assert_array_equal(np.asarray(off.ids), np.asarray(on.ids))
    c = obs_reg.registry().get("compass_queries_total")
    assert c.value(bucket="", shard="0", tenant="") == 2
    assert c.value(bucket="", shard="1", tenant="") == 2
    # the aggregate the caller sees matches the per-shard sum in the registry
    per_shard_dist = obs_reg.registry().get("compass_dist_total")
    assert per_shard_dist.value(bucket="", shard="0", tenant="") + per_shard_dist.value(
        bucket="", shard="1", tenant=""
    ) == pytest.approx(float(np.asarray(on.stats.n_dist).sum()))


# -- serving: per-batch metrics, compile events, write-error routing ----------


def _service(mutable: bool):
    from repro.core.index import BuildConfig, build_index
    from repro.core.mutable import MutableIndex
    from repro.serving.search_service import SearchService

    rng = np.random.default_rng(12)
    n, d, a = 400, 12, 4
    x = rng.normal(size=(n, d)).astype(np.float32)
    at = rng.uniform(size=(n, a)).astype(np.float32)
    cfg = BuildConfig(m=8, nlist=8, kmeans_iters=3)
    idx = MutableIndex.build(x, at, cfg, delta_cap=32) if mutable else build_index(x, at, cfg)
    pm = CompassParams(k=5, ef=32, backend="ref")
    svc = SearchService(idx, pm, batch_size=4, max_wait_s=0.0)
    return svc, rng, d, a


def test_service_records_batch_metrics():
    obs_reg.set_enabled(True)
    svc, rng, d, a = _service(mutable=False)
    for i in range(6):  # one full batch of 4 + one padded batch of 2
        svc.submit(rng.normal(size=d).astype(np.float32), P.Pred.range(0, 0.0, 0.6))
    svc.run_until_idle()
    r = obs_reg.registry()
    req = r.get("compass_serve_requests_total")
    samples = req.samples()
    assert len(samples) == 1  # one (B, T) bucket for this uniform workload
    bname = samples[0]["labels"]["bucket"]
    assert bname.startswith("B4xT")
    assert req.value(bucket=bname, tenant="") == 6
    assert r.get("compass_serve_batches_total").value(bucket=bname, tenant="") == 2
    assert r.get("compass_serve_fillers_total").value(bucket=bname, tenant="") == 2
    # queries recorded == real lanes, not padded lanes
    assert r.get("compass_queries_total").value(bucket=bname, shard="", tenant="") == 6
    _, _, n_exec = r.get("compass_serve_exec_seconds").series(bucket=bname, tenant="")
    assert n_exec == 2
    assert svc.stats()["obs_enabled"] is True
    assert svc.stats()["obs_events"].get("compile", 0) >= 1
    assert obs_reg.validate_export(r.to_json()) == []


@pytest.mark.parametrize("front_door", ["search_service", "collection_service"])
def test_loop_steps_counter_is_the_batched_loop_iterations(front_door, monkeypatch):
    """``compass_loop_steps_total`` adds, per micro-batch, the largest
    ``n_steps`` over all B lanes, fillers included: the iterations the
    batched loop ran.  Recorded only while the registry is enabled."""
    import importlib

    from repro.core.mutable import mutable_search
    from repro.serving.tenancy import CollectionService

    module = importlib.import_module(
        "repro.serving.search_service" if front_door == "search_service"
        else "repro.serving.tenancy.service")
    ran = []

    def spy(*args, **kwargs):
        res = mutable_search(*args, **kwargs)
        ran.append(np.asarray(res.stats.n_steps))
        return res

    monkeypatch.setattr(module, "mutable_search", spy)
    svc, rng, d, _ = _service(mutable=True)
    if front_door == "collection_service":
        coll = CollectionService(svc.params, batch_size=4, max_wait_s=0.0)
        coll.create("t", svc.mutable)

        def submit(q, p):
            coll.submit("t", q, p)

        drain = coll.run_until_idle
    else:
        submit, drain = svc.submit, svc.run_until_idle

    def batches(n):
        for i in range(n):
            submit(rng.normal(size=d).astype(np.float32),
                   P.Pred.range(i % 2, 0.0, 0.3 + 0.1 * (i % 3)))
        drain()

    batches(3)  # registry off: nothing recorded
    assert obs_reg.registry().get("compass_loop_steps_total") is None
    ran.clear()
    obs_reg.set_enabled(True)
    batches(6)  # a full batch of 4 and one of 2 real lanes and 2 fillers
    assert [len(r) for r in ran] == [4, 4]
    counter = obs_reg.registry().get("compass_loop_steps_total")
    assert sum(s["value"] for s in counter.samples()) == sum(int(r.max()) for r in ran)
    # the mean over real lanes can only be lower
    steps = obs_reg.registry().get("compass_steps_total")
    assert sum(s["value"] for s in steps.samples()) <= 6 * sum(int(r.max()) for r in ran)


def test_service_write_error_routing():
    obs_reg.set_enabled(True)
    svc, rng, d, a = _service(mutable=True)
    gid = 7
    svc.submit_delete(gid)
    svc.submit_delete(gid)  # raced duplicate: becomes a counted no-op
    svc.step()
    assert svc.n_write_errors == 1
    assert svc.stats()["n_write_errors"] == 1
    assert obs_reg.registry().get("compass_write_errors_total").value(tenant="") == 1
    assert obs_ev.EVENTS.counts().get("write_error") == 1
    ev = obs_ev.EVENTS.tail(1, kind="write_error")[0]
    assert ev["gid"] == gid


def test_service_compile_events_and_counter():
    obs_reg.set_enabled(True)
    svc, rng, d, a = _service(mutable=False)
    svc.submit(rng.normal(size=d).astype(np.float32), P.Pred.range(0, 0.0, 0.6))
    svc.flush()
    assert obs_reg.registry().get("compass_compiles_total").value(cache="aot") == 1
    ev = obs_ev.EVENTS.tail(1, kind="compile")[0]
    assert ev["cache"] == "aot" and ev["wall_s"] > 0
    # second identical-shape request: cache hit, no new compile event
    svc.submit(rng.normal(size=d).astype(np.float32), P.Pred.range(0, 0.0, 0.6))
    svc.flush()
    assert obs_reg.registry().get("compass_compiles_total").value(cache="aot") == 1


# -- kernel wrappers: trace scopes, fallback + autotune counters --------------


def test_kernel_fallback_and_trace_counters():
    """The wrapper counters record at call time (trace time under jit) and
    stay on even with the registry disabled — they are compile-rate-bounded."""
    from repro.kernels import ops

    rng = np.random.default_rng(13)
    queries = jnp.asarray(rng.normal(size=(2, 8)).astype(np.float32))
    cents = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
    ref_out = ops.ivf_score(queries, cents, use_pallas=False)
    r = obs_reg.registry()
    assert (
        r.get("compass_kernel_fallback_total").value(
            kernel="ivf_score", reason="use_pallas=False"
        )
        == 1
    )
    pallas_out = ops.ivf_score(queries, cents, use_pallas=True)
    assert r.get("compass_kernel_traces_total").value(kernel="ivf_score") >= 1
    np.testing.assert_allclose(
        np.asarray(ref_out), np.asarray(pallas_out), rtol=1e-5, atol=1e-5
    )


def test_metric_fallback_counter_from_backend():
    from repro.core.engine.backend import PallasBackend

    class FakeIndex:
        pass

    idx = FakeIndex()
    idx.centroids = jnp.zeros((4, 8), jnp.float32)
    PallasBackend().centroid_scores(idx, jnp.zeros((2, 8), jnp.float32), "hamming")
    c = obs_reg.registry().get("compass_kernel_fallback_total")
    assert c.value(kernel="ivf_score", reason="metric:hamming") == 1


def test_autotune_decision_counters(monkeypatch):
    from repro.kernels import autotune

    autotune.clear()
    monkeypatch.setenv("REPRO_PALLAS_AUTOTUNE", "1")
    cands = [{"rb": 2}, {"rb": 4}]
    autotune.choose("visit_step", (1, 2, 3), cands)  # no measure_fn -> default
    # a default is not cached: a later concrete call still measures
    autotune.choose("visit_step", (1, 2, 3), cands, lambda cfg: None)  # measured
    autotune.choose("visit_step", (1, 2, 3), cands)  # cached -> table
    c = obs_reg.registry().get("compass_autotune_total")
    assert c.value(kernel="visit_step", source="default") >= 1
    assert c.value(kernel="visit_step", source="measured") >= 1
    assert c.value(kernel="visit_step", source="table") >= 1
    autotune.clear()


def test_events_inactive_without_enable_or_sink():
    assert not obs_ev.EVENTS.active()
    assert obs_ev.emit("compaction", epoch=1) is None
    assert obs_ev.EVENTS.counts() == {}
