"""The quantized cell end to end on the CPU, Pallas in interpret mode: the
configuration's ``quant`` block is built and served through the program's
own entry points, its counters and readers read, and the comparison fails
where it must: under the control, and with the timed path broken.  The
readers of the device trace read a hand-made program and trace here, since
the CPU has no device trace."""
import json
import types

import numpy as np
import pytest

from bench import scopes, spec, tracing
from bench.tests import tiny
from bench.tests.test_cell import _result
from bench.tests.test_faults import _patch_dispatch

CELL = "tiny-pq16.mixed"
HOST_READERS = {"adc_per_query", "quantize_s"}
DEVICE_READERS = {"pq_score_ms", "rerank_ms", "pq_score_roofline"}

SEARCH = "jit(mutable_search)/vmap(compass/engine/open)/compass/engine/loop/while/body"
HLO = f"""HloModule jit_mutable_search, is_scheduled=true

ENTRY %main.1 (arg: f32[32,256]) -> f32[32,256] {{
  %arg = f32[32,256]{{1,0}} parameter(0)
  %custom-call.1 = f32[32,256]{{1,0}} custom-call(%arg), custom_call_target="tpu_custom_call", metadata={{op_name="{SEARCH}/compass/engine/visit/vmap(compass/pq_score)/pallas_call"}}
  %custom-call.2 = f32[32,256]{{1,0}} custom-call(%arg), custom_call_target="tpu_custom_call", metadata={{op_name="jit(mutable_search)/compass/quant/rerank/vmap(compass/filter_distance)/pallas_call"}}
  %sort.3 = f32[32,256]{{1,0}} sort(%arg), dimensions={{1}}, metadata={{op_name="jit(mutable_search)/compass/quant/rerank/sort"}}
  ROOT %copy.2 = f32[32,256]{{1,0}} copy(%arg)
}}
"""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.write_root(tmp_path_factory.mktemp("bench-root"))
    # the tempting step: trust the ADC order and distances, skip the rerank
    cfg = json.loads((root / "bench" / "configs" / "tiny-pq16.json").read_text())
    cfg["quant"]["rerank"] = "none"
    (root / "bench" / "configs" / "tiny-pq16-norerank.json").write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][-1], name="tiny-pq16-norerank",
                                 file="bench/configs/tiny-pq16-norerank.json"))
    bench["workloads"].append(dict(bench["workloads"][-1], name="tiny-pq16-norerank.mixed",
                                   config="tiny-pq16-norerank"))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _checks(root, *extra, cell=CELL):
    code, out, err = tiny.main(root, "--workload", cell, "--seed", "2147483913",
                               "--seconds", "1", "--trace", "0", "--cpu-rehearsal", *extra)
    assert code == 0, err[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    return line["correct"], line["checks"]


def test_quantized_cell_end_to_end(root):
    line, err = _result(root, "--workload", CELL, "--seed", "5", "--trace", "0")
    assert set(line["metrics"]) == {"qps", "recall_at_10", "setup_s"}
    assert line["metrics"]["recall_at_10"]["value"] >= 0.9
    # the quantization is a phase of its own: build_s keeps reading build_index alone
    assert "] quantize_index: " in err and "] build_index: " in err


def test_quantized_cell_traced(root):
    line, err = _result(root, "--workload", CELL, "--seed", "6", "--trace", "1")
    got = line["metrics"]
    for name in HOST_READERS:
        assert got[name]["value"] > 0, name
    # no device trace on the CPU: the device readers find nothing and stay out
    for name in DEVICE_READERS:
        assert name not in got and f"per-layer metric {name} read nothing" in err, name


def test_quantized_readers_on_a_hand_made_trace(monkeypatch):
    from repro.obs import profiling

    served = profiling.ServedPrograms()
    served.record("B32xT1", lambda: HLO)
    monkeypatch.setattr(profiling, "SERVED", served)
    Op = tracing.Op
    trace = tracing.Trace(
        ops={0: [Op("custom-call.1", 10, 50), Op("custom-call.2", 50, 70),
                 Op("sort.3", 70, 75), Op("copy.2", 75, 80)]},
        spans=[("compass/serve_batch/B32xT1", 0, 100)], window=(0, 100))
    run = types.SimpleNamespace(
        trace=trace, buckets={"n_batches": 1}, counters={"compass_adc_total": 1000.0},
        config=tiny.tiny_config("x", "sift1m-pq16"), peaks=spec.peaks("TPU v5 lite"))
    assert spec.load_reader("pq_score_ms")(run) == pytest.approx(40e-6)
    # the rerank's scoring kernel is a stage of its own, and part of the rerank's time
    assert scopes.stage_ms(run, "compass/filter_distance") == pytest.approx(20e-6)
    assert scopes.stage_ms(run, "compass/quant/rerank") == pytest.approx(5e-6)
    assert spec.load_reader("rerank_ms")(run) == pytest.approx(25e-6)
    # 1000 rows of 16 code bytes and a 4-byte id at 819 GB/s, over 40 ns
    assert spec.load_reader("pq_score_roofline")(run) == pytest.approx(
        100.0 * 1000 * 20 / 819e9 / 40e-9)
    # the exact tier has no quant block: its roofline reads nothing
    run.config = tiny.tiny_config("x")
    assert spec.load_reader("pq_score_roofline")(run) is None


def test_quantized_control_fails(root):
    correct, checks = _checks(root, "--control")
    assert not correct
    assert checks["dist_err"]["value"] > checks["dist_err"]["limit"]


def test_quantized_half_the_batch_left_out(monkeypatch, root):
    _patch_dispatch(monkeypatch, lambda results: results[: len(results) // 2])
    correct, checks = _checks(root)
    assert not correct
    assert checks["lost"]["value"] > 0


def test_quantized_answer_altered_where_produced(monkeypatch, root):
    def alter(results):
        for r in results:
            r.ids = np.where(r.ids >= 0, (r.ids + 1) % 1000, r.ids).astype(r.ids.dtype)
        return results

    _patch_dispatch(monkeypatch, alter)
    correct, checks = _checks(root)
    assert not correct
    assert checks["dist_err"]["value"] > checks["dist_err"]["limit"]


def test_quantized_distances_without_the_rerank_fail(root):
    correct, checks = _checks(root, cell="tiny-pq16-norerank.mixed")
    assert not correct
    assert checks["dist_err"]["value"] > checks["dist_err"]["limit"]
