"""A benchmark root at a tiny size, written into a temporary directory: its
own ``BENCHMARK.json``, configuration, traffic mix and per-layer readers.
The harness finds all of it by name, with no edit of its own files."""
from __future__ import annotations

import contextlib
import io
import json
import pathlib
import shutil

from bench import run

REAL = pathlib.Path(__file__).resolve().parents[2]


def tiny_config(name: str) -> dict:
    base = json.loads((REAL / "bench" / "configs" / "sift1m-exact.json").read_text())
    base["name"] = name
    base["corpus"].update(rows=1000, modes=8)
    base["index"]["nlist"] = 16
    base["search"]["ef"] = 16
    base["service"]["batch_size"] = 4
    return base


def write_root(tmp: pathlib.Path) -> pathlib.Path:
    """Two cells, ``tiny-exact.mixed`` (the real ``broad`` mix) and
    ``tiny-exact.conj`` (its conjunctions alone), with the real benchmark's
    metrics and one extra per-layer metric, ``batches_traced``."""
    bench = json.loads((REAL / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir(parents=True)
    shutil.copytree(REAL / "bench" / "metrics", tmp / "bench" / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REAL / "bench" / "peaks.json", tmp / "bench" / "peaks.json")
    (tmp / "bench" / "configs" / "tiny-exact.json").write_text(
        json.dumps(tiny_config("tiny-exact")))
    traffic = json.loads((REAL / "bench" / "traffic" / "broad.json").read_text())
    traffic["arrivals"]["clients"] = 8
    traffic["pool"] = 256
    (tmp / "bench" / "traffic" / "mixed.json").write_text(json.dumps(traffic))
    traffic["mix"] = traffic["mix"][:1]
    (tmp / "bench" / "traffic" / "conj.json").write_text(json.dumps(traffic))
    (tmp / "bench" / "metrics" / "batches_traced.py").write_text(
        "def read(run):\n    return float(run.buckets['n_batches']) or None\n")
    bench["configs"] = [{"name": "tiny-exact", "source": "in-test",
                         "file": "bench/configs/tiny-exact.json", "reduced": ["corpus"],
                         "why": "tiny"}]
    bench["workloads"] = [
        {"name": f"tiny-exact.{mix}", "config": "tiny-exact", "traffic": mix, "chips": 1,
         "why": "tiny"} for mix in ("mixed", "conj")]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    bench["end_to_end"][1]["workloads"] = ["tiny-exact.mixed"]
    bench["per_layer"].append({"name": "batches_traced", "unit": "batches", "better": "higher",
                               "source": "program_counter", "layer": "front door",
                               "moves": "qps"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def main(root: pathlib.Path, *argv: str):
    """``(exit code, stdout, stderr)`` of the harness run in this process."""
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        code = run.main(list(argv), root=root)
    return code, out.getvalue(), err.getvalue()
