"""The wide and the narrow cell end to end on the CPU, Pallas in interpret
mode.  ``tiny-wide`` is ``gist1m-exact`` at 1,000 rows of 960-d: its rows
take the row-gather kernels' XLA route, whose gather is a stage scope of its
own.  ``tiny-exact.narrow`` is the ``narrow`` mix
at widths that pass 20 and 60 of 1,000 rows: the planner runs PREFILTER and
the graph loop never steps.  The readers of the device trace read the
served wide program's own text under a hand-made trace here, since the CPU
has no device trace."""
import json
import types

import pytest

from bench import spec, tracing
from bench.tests import tiny
from bench.tests.test_cell import _result
from bench.tests.test_cell_quantized import HLO, _checks

WIDE = "tiny-wide.mixed"
NARROW = "tiny-exact.narrow"
SCOPE = "compass/row_gather/xla"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.write_root(tmp_path_factory.mktemp("bench-root"))
    (root / "bench" / "configs" / "tiny-wide.json").write_text(
        json.dumps(tiny.tiny_config("tiny-wide", "gist1m-exact")))
    narrow = json.loads((tiny.REAL / "bench" / "traffic" / "narrow.json").read_text())
    narrow["arrivals"]["clients"] = 8
    narrow["pool"] = 256
    narrow["mix"][0]["width"] = 0.02
    narrow["mix"][1]["width"] = 0.03
    (root / "bench" / "traffic" / "narrow.json").write_text(json.dumps(narrow))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="tiny-wide",
                                 file="bench/configs/tiny-wide.json"))
    bench["workloads"] += [
        {"name": WIDE, "config": "tiny-wide", "traffic": "mixed", "chips": 1, "why": "tiny"},
        {"name": NARROW, "config": "tiny-exact", "traffic": "narrow", "chips": 1, "why": "tiny"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_wide_cell_end_to_end(root):
    line, _ = _result(root, "--workload", WIDE, "--seed", "7", "--trace", "0")
    assert line["metrics"]["recall_at_10"]["value"] >= 0.9


def test_wide_control_fails(root):
    correct, checks = _checks(root, "--control", cell=WIDE)
    assert not correct
    assert checks["dist_err"]["value"] > checks["dist_err"]["limit"]


@pytest.fixture(scope="module")
def wide_traced(root):
    """A traced run of the wide cell: its result line and the texts of the
    programs it served."""
    from repro.obs import profiling

    line, _ = _result(root, "--workload", WIDE, "--seed", "8", "--trace", "1")
    return line, profiling.SERVED.texts()


def test_wide_rows_take_the_xla_route(wide_traced):
    """Rows of 960 float32 are scored, and every served program gathers them
    in the XLA route's scope."""
    line, texts = wide_traced
    assert line["metrics"]["dist_per_query"]["value"] > 0
    assert texts and all(SCOPE in text for text in texts.values())


def test_wide_readers_on_the_served_program(monkeypatch, wide_traced):
    """The served program puts the rows' gather in its own scope, inside the
    visit step's stage; both readers read it from a trace of those ops."""
    from bench import scopes
    from repro.obs import profiling

    _, texts = wide_traced
    label, text = next((k, v) for k, v in texts.items() if SCOPE in v)
    prog = scopes.Program(text)
    via = lambda name: prog.instrs[prog.placed(name)[1]].op_name or ""
    in_visit = [n for n in prog.instrs if prog.stage(n) == "compass/visit_step"]
    gather = next(n for n in in_visit if SCOPE in via(n))
    other = next(n for n in in_visit if SCOPE not in via(n))
    scan = next(n for n in prog.instrs
                if prog.stage(n) == "compass/filter_distance" and SCOPE not in via(n))
    served = profiling.ServedPrograms()
    served.record(label, lambda: text)
    monkeypatch.setattr(profiling, "SERVED", served)
    Op = tracing.Op
    trace = tracing.Trace(ops={0: [Op(gather, 10, 30), Op(other, 30, 70), Op(scan, 70, 90)]},
                          spans=[(f"compass/serve_batch/{label}", 0, 100)], window=(0, 100))
    run = types.SimpleNamespace(
        trace=trace, buckets={"n_batches": 1}, counters={"compass_dist_total": 1000.0},
        config=tiny.tiny_config("x", "gist1m-exact"), peaks=spec.peaks("TPU v5 lite"))
    assert spec.load_reader("xla_rows_ms")(run) == pytest.approx(20e-6)
    # 1000 rows of 960 float32, 4 float32 attributes and an int32 id at
    # 819 GB/s, over the visit step's 60 ns (the gather included) and the
    # PREFILTER scan's 20 ns
    assert spec.load_reader("visit_step_roofline")(run) == pytest.approx(
        100.0 * 1000 * (4 * 960 + 4 * 4 + 4) / 819e9 / 80e-9)
    # a program that opens no such scope (128-d rows are DMA'd): nothing to read
    served = profiling.ServedPrograms()
    served.record("B32xT1", lambda: HLO)
    monkeypatch.setattr(profiling, "SERVED", served)
    run.trace = tracing.Trace(ops={0: [Op("custom-call.1", 10, 50)]},
                              spans=[("compass/serve_batch/B32xT1", 0, 100)], window=(0, 100))
    assert spec.load_reader("xla_rows_ms")(run) is None


def test_narrow_cell_runs_prefilter(root):
    line, _ = _result(root, "--workload", NARROW, "--seed", "2147483931", "--trace", "1")
    got = line["metrics"]
    assert got["prefilter_share"]["value"] == 100.0
    assert got["steps_per_query"]["value"] == 0.0
    assert got["dist_per_query"]["value"] > 0
