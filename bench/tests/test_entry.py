"""The entry point refuses to measure without a chip, and resolves cells,
configurations, mixes and readers by name."""
import json

import pytest

from bench import spec
from bench.tests import tiny


def test_main_without_a_tpu_fails(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    code, out, err = tiny.main(tiny.REAL, "--workload", "sift1m-exact.broad", "--seed", "1",
                               "--seconds", "1", "--trace", "0")
    assert code != 0
    assert out == ""
    assert "no TPU found" in err and "cpu" in err


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_every_declared_cell_resolves():
    bench = json.loads((tiny.REAL / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert {m["name"] for m in cell.end_to_end} >= {"qps", "recall_at_10", "setup_s"}
        for m in cell.per_layer:
            assert callable(spec.load_reader(m["name"]))


def test_a_cell_added_from_files(tmp_path):
    root = tiny.write_root(tmp_path)
    cell = spec.load_cell("tiny-exact.conj", root)
    assert cell.config["corpus"]["rows"] == 1000 and cell.traffic["pool"] == 256
    assert [s["terms"] for s in cell.traffic["mix"]] == [1]
    assert "batches_traced" in {m["name"] for m in cell.per_layer}
    assert "p95_ms" not in {m["name"] for m in cell.end_to_end}
    assert spec.load_reader("batches_traced", root)(
        type("Run", (), {"buckets": {"n_batches": 3}})()) == 3.0
    with pytest.raises(KeyError):
        spec.load_cell("tiny-absent.mixed", root)


def test_benchmark_json_names_and_units():
    import re

    bench = json.loads((tiny.REAL / "BENCHMARK.json").read_text())
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
    for w in bench["workloads"]:
        assert name.match(w["name"]) and len(w["why"]) <= 200
    for c in bench["configs"]:
        assert name.match(c["name"]) and len(c["source"]) <= 200 and len(c["why"]) <= 200


QUANT = {"m": 16, "ks": 256, "iters": 10, "refine_factor": 4, "rerank": "full"}


@pytest.mark.parametrize("change", [{"quant": {"m": 16, "ks": 256}}, {"tier": {"kind": "lsm"}},
                                    {"quant": dict(QUANT, nbits=8)}])
def test_a_configuration_the_harness_cannot_drive_is_refused(change):
    from bench import system

    cfg = dict(tiny.tiny_config("x"), **change)
    with pytest.raises(ValueError):
        system.build(cfg, None, None, 1, {})


def test_a_metric_without_a_reader_is_an_error(tmp_path):
    root = tiny.write_root(tmp_path)
    with pytest.raises(FileNotFoundError):
        spec.load_reader("absent_metric", root)


@pytest.mark.parametrize("real", ["sift1m-exact", "sift1m-pq16"])
def test_the_build_follows_the_quant_block(monkeypatch, real):
    """Without a quant block nothing is quantized and the collection searches
    the full-precision rows, as before; with one, the built index is
    quantized as the block says and searched in two stages."""
    import repro.core.quant as quant
    from bench import data, system

    cfg = tiny.tiny_config("x", real)
    if cfg["quant"] is None:
        monkeypatch.setattr(quant, "quantize_index", None)  # never called
    x, attrs, _ = data.make_corpus(1, cfg["corpus"], 1)
    phases = {}
    svc, name = system.build(cfg, x, attrs, 1, phases)
    col = svc._collections[name]
    qvecs = col.mutable.snapshot().index.qvecs
    if cfg["quant"] is None:
        assert set(phases) == {"build_index", "mutable_wrap"}
        assert col.params.quant is None and qvecs is None
    else:
        assert set(phases) == {"build_index", "quantize_index", "mutable_wrap"}
        assert col.params.quant == quant.QuantParams(refine_factor=4, rerank="full")
        assert (qvecs.m, qvecs.ks) == (16, 256)
