"""One cell end to end on the CPU, Pallas in interpret mode: the harness's
last line follows the contract, with the metric names BENCHMARK.json
declares, and the cell comes from files the harness finds by name."""
import json

import pytest

from bench.tests import tiny

CONTRACT = ("correct", "attempted", "failed", "metrics", "device")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.write_root(tmp_path_factory.mktemp("bench-root"))


def _result(root, *argv):
    code, out, err = tiny.main(root, *argv, "--seconds", "1.5", "--cpu-rehearsal")
    assert code == 0, err[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    for key in CONTRACT:
        assert key in line
    assert line["correct"] is True, err[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    # the compared numbers close standard error, each beside its limit
    assert err.strip().splitlines()[-1].split("] ")[1].startswith("check lost = 0")
    return line, err


def test_exact_cell_end_to_end(root):
    line, _ = _result(root, "--workload", "tiny-exact.mixed", "--seed", "3", "--trace", "0")
    assert set(line["metrics"]) == {"qps", "p95_ms", "recall_at_10", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0
    assert line["metrics"]["recall_at_10"]["value"] >= 0.9
