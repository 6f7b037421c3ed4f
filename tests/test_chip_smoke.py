"""chip_smoke.py refuses to run, and prints no result line, off a TPU."""
import importlib.util
import pathlib

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_no_tpu_means_no_result(capsys):
    assert jax.devices()[0].platform != "tpu"
    for argv in ([], ["--chips", "4"]):
        assert _load().main(argv) == 2
        out = capsys.readouterr()
        assert out.out == "" and "no TPU found" in out.err


def test_defaults_are_the_deployment_size():
    cs = _load()
    one = cs.parse_args([])
    assert (one.rows, one.queries, one.batch, one.chips) == (1_000_000, 256, 32, 1)
    assert cs.parse_args(["--chips", "4"]).rows == 400_000
    assert cs.parse_args(["--cpu-rehearsal"]).rows == 4_000
    assert cs.parse_args(["--cpu-rehearsal", "--chips", "4"]).rows == 8_000
    # sizes are fixed per path: no option changes what the smoke runs
    with pytest.raises(SystemExit):
        cs.parse_args(["--rows", "1000"])
